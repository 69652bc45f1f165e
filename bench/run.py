#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the machine this is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the server as `hocuspocus_tpu.cli` wires `--tpu-serve`, in this
process, which holds the chip; lets it recover its documents from a
write-ahead log made from the seed; drives it from client processes of its
own (`lib/clients.py`) with real providers over loopback websockets for
`--seconds`; and prints as the last line of standard output one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `compared`, every number that decided `correct` beside
its limit. No accelerator, too few chips, no program beside the benchmark,
or a failed health fact: a message on standard error, a non-zero exit and no
result line.

For the builder, never passed by the driver: `--rehearse` (the same code at
a tiny size on whatever JAX finds; prints no metric) and `--control <name>`
(also judges the reference with a broken guarantee put in the program's
place, see lib/compare.py).
"""

from __future__ import annotations

import time

_STARTED = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import random  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "lib"))

from manifest import ROOT, Manifest, ManifestError  # noqa: E402
from stats import percentile  # noqa: E402

EXIT_RUN_FAILED = 1
EXIT_NO_ACCELERATOR = 3
EXIT_NO_PROGRAM = 4
GRACE_SECONDS = 60.0  # how long an update may still arrive after the window
TRACE_SECONDS = 10.0  # a traced run traces this much, the end of the window
RESIDENT_SAMPLE = 32  # documents at rest that are read back and compared


def log(message: str) -> None:
    print(f"[bench +{time.monotonic() - _STARTED:6.1f}s] {message}", file=sys.stderr, flush=True)


def parse(argv) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--control", action="append", default=[])
    return parser.parse_args(argv)


def settings(args, manifest: Manifest) -> "tuple[dict, dict, dict]":
    """(cell, configuration, traffic mix) as this run uses them."""
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    if args.rehearse:
        config = {**config, **config["rehearse"]}
        mix = {**mix, **mix["rehearse"]}
    for key in ("clients_per_doc", "writers_per_doc", "doc_units"):
        mix[key] = config[key]
    mix["docs_per_plane"] = config["driven_docs_per_plane"]
    return cell, config, mix


class Clients:
    """The client processes of a run (`lib/clients.py`), each with a share
    of the driven documents, and the lines exchanged with them."""

    def __init__(
        self, run_dir: str, url: str, names: "list[str]", mix: dict, document: str, seed: int, seconds: float
    ) -> None:
        self.specs = []
        count = max(1, min(int(mix.get("client_processes", 1)), len(names)))
        for nth in range(count):
            spec = {
                "root": ROOT,
                "generator": mix["generator"],
                "url": url,
                "mix": mix,
                "document": document,
                "seed": seed,
                "seconds": seconds,
                "grace_seconds": GRACE_SECONDS,
                "all_docs": len(names),
                "clients_per_doc": mix["clients_per_doc"],
                "writers_per_doc": mix["writers_per_doc"],
                "docs": [{"index": i, "name": names[i]} for i in range(nth, len(names), count)],
                "result": os.path.join(run_dir, f"clients-{nth}.pickle"),
            }
            path = os.path.join(run_dir, f"clients-{nth}.json")
            with open(path, "w") as fh:
                json.dump(spec, fh)
            self.specs.append(path)
        self.processes: list = []

    async def start(self) -> None:
        for path in self.specs:
            self.processes.append(
                await asyncio.create_subprocess_exec(
                    sys.executable,
                    os.path.join(HERE, "lib", "clients.py"),
                    path,
                    stdin=asyncio.subprocess.PIPE,
                    stdout=asyncio.subprocess.PIPE,
                )
            )

    async def tell(self, line: str) -> None:
        for process in self.processes:
            process.stdin.write(line.encode() + b"\n")
            await process.stdin.drain()

    async def hear(self, word: str, timeout: float) -> "list[str]":
        """The line that starts with `word`, from every process."""
        from serve import RunFailed

        async def one(process) -> str:
            line = (await asyncio.wait_for(process.stdout.readline(), timeout)).decode().strip()
            if not line.startswith(word):
                raise RunFailed(f"a client process said {line!r} where {word!r} was due")
            return line

        return list(await asyncio.gather(*(one(process) for process in self.processes)))

    async def stop(self) -> None:
        for process in self.processes:
            if process.returncode is None:
                with contextlib.suppress(Exception):
                    process.stdin.write(b"exit\n")
                    await process.stdin.drain()
        for process in self.processes:
            try:
                await asyncio.wait_for(process.wait(), 20)
            except asyncio.TimeoutError:
                process.kill()
                await process.wait()


async def drive(args, config: dict, kind, mix: dict, seconds: float, compiles) -> dict:
    """Set-up, the window, and everything read from the live system; the
    server and the client processes are gone when this returns."""
    import jax

    import kinds
    from serve import LoopClock, RunFailed, Served

    clock = time.monotonic  # CLOCK_MONOTONIC: the client processes read the same one
    run_dir = tempfile.mkdtemp(prefix="bench-run-")
    trace_dir = os.path.join(ROOT, "bench_out", "trace")
    loop = asyncio.get_running_loop()
    loop_clock = LoopClock(loop, jax.profiler.TraceAnnotation if args.trace else None)
    served = Served(config["flags"])
    clients = None
    traced = None
    seen: dict = {}
    try:
        await served.boot()
        log(
            f"READY: {len(served.planes)} plane(s) of {served.planes[0].num_docs} x "
            f"{served.planes[0].capacity}; warm grid {served.warm.get('entries')} entries in "
            f"{served.warm.get('seconds')}s; {compiles.cache_hits} of {len(compiles.programs)} "
            "programs from the compile cache"
        )
        units = int(config["doc_units"])
        driven = served.pick_names(int(mix["docs_per_plane"]), args.seed, "bench")
        resident = served.pick_names(int(config["resident_docs_per_plane"]) - int(mix["docs_per_plane"]), args.seed, "rest")
        firsts = kind.first_states(args.seed, len(resident) + len(driven), config)
        started = clock()
        written = await served.write_log(resident + driven, firsts, kind)
        wrote_s = clock() - started
        clients = Clients(run_dir, served.url, driven, mix, kinds.name_of(config), args.seed, seconds)
        await clients.start()  # they import while the server recovers its documents
        started = clock()
        await served.recover(resident + driven)
        log(
            f"recovered {len(resident) + len(driven)} documents of {units} units from {written} bytes "
            f"of log (written in {wrote_s:.1f}s) in {clock() - started:.1f}s: {len(driven)} driven, {len(resident)} at rest"
        )
        await clients.hear("ready", 120)
        await clients.tell("connect")
        await clients.hear("connected", 300)
        await served.settled()
        log(
            f"{len(driven)} documents x {mix['clients_per_doc']} clients connected from "
            f"{len(clients.processes)} client process(es); the overload ladder is at green"
        )

        pauses: "list[float]" = []  # seconds the server's collector held the loop, window only
        gc_started = time.perf_counter()

        def on_gc(phase: str, _info: dict) -> None:
            nonlocal gc_started
            if phase == "start":
                gc_started = time.perf_counter()
            elif "opened" in seen and "closed" not in seen:
                pauses.append(time.perf_counter() - gc_started)

        gc.callbacks.append(on_gc)
        lags: "list[float]" = []

        async def sample_loop_lag() -> None:
            while True:
                before = loop.time()
                await asyncio.sleep(0.01)
                if "opened" in seen and "closed" not in seen:
                    lags.append(max(loop.time() - before - 0.01, 0.0))

        async def trace_the_tail(closes_at: float) -> None:
            """The profiler runs over the window's last TRACE_SECONDS: what it
            costs to stop grows with the device events it holds. It starts and
            stops off the loop, so that the server's watchdogs do not read
            either as a stall."""
            await asyncio.sleep(max(closes_at - TRACE_SECONDS - clock(), 0))
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            await loop.run_in_executor(
                None, lambda: jax.profiler.start_trace(trace_dir, profiler_options=options)
            )
            seen["trace"] = (clock(), served.counters()["dispatch"])

        sampler = asyncio.ensure_future(sample_loop_lag())
        opens_at = clock() + float(mix["warmup_seconds"]) + 0.5
        closes_at = opens_at + seconds
        await clients.tell(f"go {opens_at!r}")
        tracer = asyncio.ensure_future(trace_the_tail(closes_at)) if args.trace else None
        await asyncio.sleep(max(opens_at - clock(), 0))
        seen["counters"] = served.counters()
        seen["compiled"] = len(compiles.programs)
        seen["asleep"] = loop_clock.asleep_s
        seen["opened"] = clock()
        await asyncio.sleep(max(closes_at - clock(), 0))
        closed = seen["closed"] = clock()
        after = served.counters()
        asleep_s = loop_clock.asleep_s - seen["asleep"]
        compiled = compiles.programs[seen["compiled"] :]
        sampler.cancel()
        if tracer is not None:
            await tracer
            traced = {
                "window_s": clock() - seen["trace"][0],
                "dispatch": (seen["trace"][1], served.counters()["dispatch"]),
            }
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            log(f"traced the window's last {traced['window_s']:.1f}s; the profiler has stopped")
        log(f"window closed after {closed - seen['opened']:.2f}s; waiting for what is still in flight")
        results = []
        for line in await clients.hear("done", GRACE_SECONDS + seconds + 60):
            with open(line.split(None, 1)[1], "rb") as fh:
                results.append(pickle.load(fh))
        await served.quiesce()
        server_views, device_views = await served.views(driven, kind)
        logs = await served.logged(driven)
        sample = random.Random(args.seed ^ 0xA7E57).sample(range(len(resident)), min(RESIDENT_SAMPLE, len(resident)))
        rest_server, rest_device = await served.views([resident[i] for i in sample], kind)
        by_doc: dict = {}
        for result in results:
            by_doc.update(result["clients"])
        observed = {
            "undelivered": sum(r["undelivered"] + len(r["unsent"]) for r in results),
            "docs": [
                {
                    "clients": by_doc[doc],
                    "server": server_views[name],
                    "device": device_views[name],
                    "wal": logs[name],
                }
                for doc, name in enumerate(driven)
            ],
            "resident": [
                (kind.first_view(firsts[i]), rest_server[resident[i]], rest_device[resident[i]]) for i in sample
            ],
        }
        health = served.health(resident + driven, seen["counters"], after, compiled)
        rungs = served.rungs_between(seen["counters"], after)
        memory = [d.memory_stats() or {} for d in jax.local_devices()]
        latency = [
            (result["gave_up"] if done is None else done) - start
            for result in results
            for _doc, start, done in result["records"]
        ] + [result["gave_up"] - due for result in results for due in result["unsent"]]
        records = [record for result in results for record in result["records"]]
        late = [s for result in results for s in result["late_s"]]
        log(
            f"overload ladder over the window: {' -> '.join(rungs)}; the server's loop busy "
            f"{100 * (1 - asleep_s / seconds):.0f} %, lag p95 {1000 * (percentile(lags, 0.95) or 0):.1f} ms, "
            f"longest collector pause {1000 * max(pauses, default=0):.0f} ms; clients' CPU "
            f"{[round(100 * r['cpu_s'] / seconds) for r in results]} %, late p95 "
            f"{1000 * (percentile(late, 0.95) or 0):.1f} ms; p50 {1000 * (percentile(latency, 0.5) or 0):.1f} ms"
        )
        return {
            "health": health,
            "compiled_in_window": compiled,
            "observed": observed,
            "first": firsts[len(resident) :],
            "log": [entry for result in results for entry in result["log"]],
            "logs": [logs[name] for name in driven],
            "setup_s": opens_at - _STARTED,
            "memory_peak_bytes": max((m.get("peak_bytes_in_use") or 0) for m in memory),
            "latency_s": latency,
            "attempted": len(latency),
            "failed": sum(1 for _doc, _start, done in records if done is None)
            + sum(len(result["unsent"]) for result in results),
            "delivered_in_window": sum(1 for _doc, _start, done in records if done is not None and done <= closes_at),
            "readings": {
                "seconds": seconds,
                "open_loop": results[0]["open_loop"],
                "offered": len(latency),
                "late_s": late,
                "latency_s": latency,
                "loop_lag_s": lags,
                "loop_asleep_s": asleep_s,
                "gc_pause_s": pauses,
                "clients_cpu_s": [result["cpu_s"] for result in results],
                "plane_delta": served.plane_delta(seen["counters"], after),
                "wal_delta": {k: v - seen["counters"]["wal"].get(k, 0) for k, v in after["wal"].items()},
                "dispatch": (seen["counters"]["dispatch"], after["dispatch"]),
                "doc_units": units,
                "arena": served.planes[0].arena,
                "row_capacity": served.planes[0].capacity,
                "planes": len(served.planes),
            },
            "traced": traced,
            "trace_dir": trace_dir,
        }
    finally:
        with contextlib.suppress(NameError, ValueError):
            gc.callbacks.remove(on_gc)
        if "trace" in seen and traced is None:  # the run broke off with the profiler on
            with contextlib.suppress(Exception):
                jax.profiler.stop_trace()
        if clients is not None:
            await clients.stop()
        await served.close()
        shutil.rmtree(run_dir, ignore_errors=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        manifest = Manifest()
        cell, config, mix = settings(args, manifest)
    except ManifestError as error:
        print(f"bench: {error}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "hocuspocus_tpu")):
        print(f"bench: no hocuspocus_tpu package in {ROOT}: nothing to measure", file=sys.stderr)
        return EXIT_NO_PROGRAM
    sys.path.insert(1, ROOT)
    seconds = args.seconds if args.seconds is not None else float(manifest.data["run_seconds"])
    import room

    refused = room.refusal(config, mix, seconds)
    if refused:
        print(f"bench: {refused}", file=sys.stderr)
        return 2
    kind = manifest.kind(config)
    with contextlib.suppress(ImportError, ValueError, OSError):
        import resource

        _soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and (device["platform"] != "tpu" or len(devices) < cell["chips"]):
        print(
            f"bench: the cell needs {cell['chips']} TPU chip(s); JAX found {device}. Nothing was "
            "run. Use the chip tool, or --rehearse for a tiny run that prints no metric.",
            file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR
    peaks = json.load(open(os.path.join(HERE, "peaks.json")))
    if not args.rehearse and device["kind"] not in peaks:
        print(f"bench: no peaks for device kind {device['kind']!r} in bench/peaks.json", file=sys.stderr)
        return EXIT_RUN_FAILED

    from serve import CompileEvents, RunFailed

    compiles = CompileEvents()
    from hocuspocus_tpu import native

    native.build()
    if native.get_codec() is None or native.codec_status()[0] != "native":
        print(f"bench: the native codec did not build: {native.codec_status()}", file=sys.stderr)
        return EXIT_RUN_FAILED
    import hocuspocus_tpu.tpu.kernels  # noqa: F401  (places the compile cache inside the checkout)

    log(f"{args.workload} seed {args.seed}: {device}; codec built; booting {' '.join(config['flags'])}")
    try:
        # The CLI's Logger extension prints a line per connection and per change
        # to stdout, which carries the result alone: its lines are formatted
        # as in a deployment and go nowhere, so that no pipe's reader paces the run.
        with open(os.devnull, "w") as nowhere, contextlib.redirect_stdout(nowhere):
            run = asyncio.run(drive(args, config, kind, mix, seconds, compiles))
    except (RunFailed, TimeoutError) as error:
        print(f"bench: the run failed: {error}", file=sys.stderr)
        return EXIT_RUN_FAILED
    if args.rehearse:
        # How far the overload ladder climbs on a borrowed CPU, and whether the
        # program's warm grid covers a toy arena's batch shapes when that CPU
        # is slow, say nothing of this code: logged, not judged.
        for fact in ("admission_never_refused", "no_compile_in_window"):
            if not run["health"].pop(fact):
                log(f"rehearsal: {fact} did not hold ({run['compiled_in_window']})")
    unhealthy = sorted(fact for fact, ok in run["health"].items() if not ok)
    if unhealthy:
        print(
            f"bench: the run did not measure the device path: {unhealthy} "
            f"(compiled in the window: {run['compiled_in_window']})",
            file=sys.stderr,
        )
        return EXIT_RUN_FAILED

    # the server and its arena are gone: the reference runs now, on the host
    import compare

    checking = time.perf_counter()
    # a mix without `position_mix` is not one of pure appends; one without
    # `replace_share` types over nothing
    only_appends = set(mix.get("position_mix", ())) == {"end"} and not mix.get("replace_share")
    reference = compare.merged(run["first"], run["log"], kind=kind)
    compared = compare.compare(reference, run["observed"], run["first"], run["log"], only_appends, kind=kind)
    controls = {}
    for name in args.control:
        broken = compare.merged(run["first"], run["log"], name, kind=kind)
        controls[name] = compare.compare(reference, compare.as_observed(broken, run["logs"], name, kind=kind), kind=kind)
    log(f"reference merged and compared in {time.perf_counter() - checking:.1f}s")

    readings = run["readings"]
    result: dict = {
        "correct": compare.correct(compared),
        "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": {},
        "device": {**device, "memory_peak_bytes": run["memory_peak_bytes"]},
    }
    values = {
        "update_to_peer_p95_ms": (percentile(run["latency_s"], 0.95) or 0.0) * 1000.0,
        "update_to_peer_p50_ms": (percentile(run["latency_s"], 0.5) or 0.0) * 1000.0,
        "updates_delivered_per_s": run["delivered_in_window"] / seconds,
        "setup_s": run["setup_s"],
    }
    if args.trace:
        import tracereduce as trace_reduction

        planes = trace_reduction.load(run["trace_dir"])
        if args.rehearse:  # no device here: the host's plane stands in, to run the same code
            reduced = trace_reduction.reduce(planes, run["traced"]["window_s"], re.compile("^/host:CPU$"))
        else:
            reduced = trace_reduction.reduce(planes, run["traced"]["window_s"])
        shutil.rmtree(run["trace_dir"], ignore_errors=True)
        readings.update(trace=reduced, traced_dispatch=run["traced"]["dispatch"], peaks=peaks.get(device["kind"]))
        result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        values = {m["name"]: manifest.reader(m["name"])(readings) for m in manifest.metrics_of(cell["name"], "per_layer")}
    group = "per_layer" if args.trace else "end_to_end"
    numbers = {
        metric["name"]: {"value": values[metric["name"]], "unit": metric["unit"]}
        for metric in manifest.metrics_of(cell["name"], group)
        if values.get(metric["name"]) is not None
    }
    if args.rehearse:
        # a rehearsal proves the code, not the system: its numbers carry no metric's name
        log(f"rehearsal numbers, not metrics: {json.dumps(list(numbers.values()))}")
    else:
        result["metrics"] = numbers
    if controls:
        result["controls"] = {name: {"correct": compare.correct(c), "compared": c} for name, c in controls.items()}
    result["compared"] = compared
    for name, (value, limit) in compared.items():
        print(f"compared: {name} = {value} (limit {limit})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # a hang has to end as a traceback and a non-zero exit inside the caller's limit
    faulthandler.dump_traceback_later(1150, exit=True)
    sys.exit(main())
