"""Server with the TPU merge plane as the SERVING path.

Documents live on device-resident arenas (one row per sequence — plain
and rich text, ProseMirror trees, arrays; maps host-side): updates from
all documents are integrated in micro-batched kernel steps, SyncStep2
replies are served from device state with storm-batched state-vector
triage, and fan-out rides one merged broadcast per flush. Device steps
run off the event loop; flush shapes pre-compile at listen. Any
degradation falls the affected doc back to the CPU path with no data
loss (see docs/tpu/merge-plane.md).

Run: python examples/tpu_merge.py
Multi-chip: pass mesh=hocuspocus_tpu.tpu.sharding.make_mesh() to shard
the arenas over the available devices.
"""

import asyncio

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hocuspocus_tpu import Configuration, Server  # noqa: E402
from hocuspocus_tpu.extensions import Logger  # noqa: E402
from hocuspocus_tpu.tpu import TpuMergeExtension  # noqa: E402


async def main() -> None:
    server = Server(
        Configuration(
            name="tpu-merge",
            extensions=[
                Logger(),
                TpuMergeExtension(num_docs=1024, capacity=4096, flush_interval_ms=5, serve=True),
            ],
        )
    )
    await server.listen(port=8000)
    await asyncio.Event().wait()


if __name__ == "__main__":
    asyncio.run(main())
