"""A client process: the load generator that a traffic mix names, run apart
from the server so that the server keeps its own core and its own heap.

    python3 bench/lib/clients.py <spec.json>

The server's process (`run.py`) writes the spec, starts this, and talks to
it in lines: it sends `connect`, `go <monotonic time the window opens>` and
`exit`; this answers `ready`, `connected` and `done <result file>`. The
result is a pickle of plain types (`Generator.result`). This process never
touches JAX: the chip belongs to the server.
"""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import os
import pickle
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_generator(name: str):
    """The module bench/generators/<name>.py."""
    path = os.path.join(os.path.dirname(HERE), "generators", name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"traffic generator {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location("bench_generator_" + name.replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def say(word: str) -> None:
    print(word, flush=True)


async def main(spec_path: str) -> int:
    with open(spec_path) as fh:
        spec = json.load(fh)
    sys.path.insert(1, spec["root"])
    os.environ["JAX_PLATFORMS"] = "cpu"  # should anything here import JAX, it finds no chip to hold
    # This heap is the yardstick's, not the system's: the clients' documents
    # are never garbage, and a pass of the collector over them would only
    # stall the clock that measures the server.
    gc.disable()
    generator = load_generator(spec["generator"]).Generator(spec)
    loop = asyncio.get_running_loop()
    say("ready")
    try:
        while True:
            line = (await loop.run_in_executor(None, sys.stdin.readline)).split()
            if not line or line[0] == "exit":
                return 0
            if line[0] == "connect":
                await generator.connect()
                say("connected")
            elif line[0] == "go":
                await generator.run(float(line[1]))
                with open(spec["result"], "wb") as fh:
                    pickle.dump(generator.result(), fh, protocol=pickle.HIGHEST_PROTOCOL)
                say("done " + spec["result"])
    finally:
        generator.close()
        await asyncio.sleep(0.2)


if __name__ == "__main__":
    sys.exit(asyncio.run(main(sys.argv[1])))
