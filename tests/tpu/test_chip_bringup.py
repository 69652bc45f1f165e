"""What makes a device failure loud, and a chip run findable again.

- a failing warm-grid shape is kept (site, shape, error) where /healthz
  and the counters read it, and the rest of the grid still compiles;
- the persistent compile cache is placed from outside
  (JAX_COMPILATION_CACHE_DIR) or at <checkout>/.jax_cache, never moved;
- `python -m hocuspocus_tpu.loadgen` runs on the platform the
  environment selects and names it;
- more device cells than chips is an error off the CPU platform;
- `chip_smoke.py` without a chip exits non-zero and runs nothing;
- the kernel result checks it runs on the chip hold in interpret mode.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from hocuspocus_tpu.tpu import SupervisedTpuMergeExtension
from hocuspocus_tpu.tpu.supervisor import STATE_READY

from tests.utils import new_hocuspocus, wait_for

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# -- warm-grid failures --------------------------------------------------------


async def test_failing_warm_shape_is_recorded_and_grid_continues(monkeypatch):
    import aiohttp

    import hocuspocus_tpu.tpu.merge_plane as mp
    from hocuspocus_tpu.tpu.scheduler import reset_warm_registry

    reset_warm_registry()
    real = mp.MergePlane.warmup_compiles

    def refuse_one(self, shape=None, shared=False):
        if shape == ("append", 16, 4):
            raise RuntimeError("RESOURCE_EXHAUSTED: simulated 17.86G of 15.75G hbm")
        return real(self, shape, shared)

    monkeypatch.setattr(mp.MergePlane, "warmup_compiles", refuse_one)
    ext = SupervisedTpuMergeExtension(serve=True, num_docs=16, capacity=256)
    server = await new_hocuspocus(extensions=[ext])
    try:
        await wait_for(lambda: ext.supervisor.state == STATE_READY, timeout=60)
        plane = ext.plane
        await wait_for(lambda: plane.warm_stats["done"], timeout=120)
        grid = plane.warmup_shapes() + plane.warmup_aux_shapes()
        serving = ext.runtime.serving
        # every other entry was still attempted, and compiled
        assert plane.warm_stats["entries"] == len(grid) + len(
            serving._gather_widths()
        ) + len(serving._TRIAGE_WIDTHS)
        assert plane.warm_stats["compiled"] == plane.warm_stats["entries"] - 1
        assert plane.counters["warm_failures"] == 1
        (failure,) = plane.warm_failures
        assert failure["site"] == "append_sparse" and failure["shape"] == "16x4"
        assert "RESOURCE_EXHAUSTED" in failure["error"]
        assert plane.compile_watch.snapshot()["warmed"]
        # ... and health says so while the supervisor is otherwise READY
        async with aiohttp.ClientSession() as session:
            async with session.get(f"{server.http_url}/healthz") as response:
                body = await response.json()
        section = body["extensions"]["SupervisedTpuMergeExtension"]
        assert body["status"] == "degraded" and section["state"] == "ready"
        assert section["degraded"] is True
        assert section["warm"]["failures"] == [{"plane": 0, **failure}]
    finally:
        await server.destroy()
        reset_warm_registry()


async def test_clean_warm_grid_reports_done_and_healthy():
    from hocuspocus_tpu.tpu.scheduler import reset_warm_registry

    reset_warm_registry()
    ext = SupervisedTpuMergeExtension(serve=True, num_docs=16, capacity=256)
    server = await new_hocuspocus(extensions=[ext])
    try:
        await wait_for(lambda: ext.supervisor.state == STATE_READY, timeout=60)
        await wait_for(lambda: ext.plane.warm_stats["done"], timeout=120)
        health = ext.supervisor.snapshot()
        assert health["degraded"] is False
        assert health["warm"]["done"] and health["warm"]["failures"] == []
        assert health["warm"]["covered"] == 0 and health["warm"]["compiled"] > 0
    finally:
        await server.destroy()
        reset_warm_registry()


# -- compile cache placement ---------------------------------------------------

# compiles one program nobody compiled before (the constant is the
# caller's) and prints [directory the code configured, compile requests
# that consulted the persistent cache, of which it answered]
_COMPILE_ONCE = """
import json, sys, jax, jax.monitoring
seen = {"requests": 0, "hits": 0}
def count(event, **_):
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        seen["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        seen["hits"] += 1
jax.monitoring.register_event_listener(count)
import hocuspocus_tpu.tpu.kernels as kernels
salt, x = int(sys.argv[1]), jax.numpy.arange(8)
seen.update(requests=0, hits=0)
jax.jit(lambda x: x * salt + 1)(x).block_until_ready()
print(json.dumps([kernels.COMPILE_CACHE_DIR, seen["requests"], seen["hits"]]))
"""


def _salt():
    """A constant no earlier run left a program for in the cache."""
    return time.time_ns() % 2**30


def _compile_once(cwd, salt, checkout, **env_changes):
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    env.update(PYTHONPATH=str(checkout), JAX_PLATFORMS="cpu", **env_changes)
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE_ONCE, str(salt)],
        env=env,
        cwd=cwd,  # never the checkout: the path must not follow the cwd
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _entries(directory):
    return set(os.listdir(directory)) if os.path.isdir(directory) else set()


def _private_checkout(tmp_path):
    """A checkout only this test's children import from: the package is
    linked in, so `<checkout>/.jax_cache` is a directory no neighbour
    test (six workers share the real one) can write into."""
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    os.symlink(os.path.join(REPO, "hocuspocus_tpu"), checkout / "hocuspocus_tpu")
    return checkout


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache whatever the cwd, a sub-second
    program is written there, and a second process reads it back."""
    checkout = _private_checkout(tmp_path)
    home = str(checkout / ".jax_cache")
    salt = _salt()
    assert _compile_once(tmp_path, salt, checkout) == [home, 1, 0]
    assert _entries(home)
    (tmp_path / "elsewhere").mkdir()
    assert _compile_once(tmp_path / "elsewhere", salt, checkout) == [home, 1, 1]


def test_compile_cache_env_var_is_left_to_jax(tmp_path):
    checkout = _private_checkout(tmp_path)
    placed = str(tmp_path / "placed")
    home = str(checkout / ".jax_cache")
    configured, requests, hits = _compile_once(
        tmp_path, _salt(), checkout, JAX_COMPILATION_CACHE_DIR=placed
    )
    assert configured is None  # the code set nothing ...
    assert (requests, hits) == (1, 0)
    assert _entries(placed)  # ... JAX read the variable itself
    assert not os.path.exists(home)  # and the default home was never made
    # the environment's own compile-time floor is respected too: this
    # sub-second program is then not worth an entry
    floored = str(tmp_path / "floored")
    _compile_once(
        tmp_path,
        _salt(),
        checkout,
        JAX_COMPILATION_CACHE_DIR=floored,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="60",
    )
    assert not _entries(floored)


# -- loadgen names its platform and forces none --------------------------------


def test_loadgen_cli_keeps_the_environments_platform(monkeypatch, capsys):
    from hocuspocus_tpu.loadgen import __main__ as cli
    from hocuspocus_tpu.loadgen import runner

    async def fake_run(self):
        return {"metric": "scenario_slo_verdict", "verdict": "pass"}

    monkeypatch.setattr(runner.ScenarioRunner, "run", fake_run)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert cli.main(["--scenario", "smoke", "--seed", "7"]) == 0
    assert "JAX_PLATFORMS" not in os.environ
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["platform"] == "cpu" and result["device_kind"]
    assert result["device_count"] >= 1


# -- device roster ---------------------------------------------------------------


def test_enumerate_devices_refuses_to_wrap_off_cpu(monkeypatch):
    import jax

    from hocuspocus_tpu.tpu.sharding import enumerate_devices

    class Chip:
        platform = "tpu"

    chips = [Chip(), Chip()]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    assert enumerate_devices(0) == chips
    assert enumerate_devices(2) == chips
    assert enumerate_devices(1) == chips[:1]
    with pytest.raises(ValueError, match="4 device cells requested but only 2 tpu"):
        enumerate_devices(4)


def test_enumerate_devices_still_wraps_on_cpu():
    import jax

    from hocuspocus_tpu.tpu.sharding import enumerate_devices

    roster = enumerate_devices(len(jax.local_devices()) + 3)
    assert len(roster) == len(jax.local_devices()) + 3


# -- chip_smoke.py ---------------------------------------------------------------


def test_chip_smoke_without_a_chip_exits_nonzero_and_runs_nothing():
    started = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""  # no result line, nothing ran
    assert "platform 'cpu'" in proc.stderr and "Nothing was run" in proc.stderr
    assert "native codec" not in proc.stderr  # stopped before any set-up
    assert time.monotonic() - started < 60


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=tmp_path,
        env=env,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 4 and proc.stdout.strip() == ""


def test_chip_smoke_names_a_program_compiled_after_its_mark():
    """`no_fresh_compile_in_traffic` rests on a jax.monitoring event:
    if a JAX upgrade renames it, this fails before the check goes
    quietly vacuous."""
    import jax

    import chip_smoke

    events = chip_smoke.CompileEvents()
    jax.jit(lambda x: x + 1)(jax.numpy.arange(4))
    mark = events.mark()
    assert events.names_since(mark) == []

    def stray_program(x):
        return x * 3

    jax.jit(stray_program)(jax.numpy.arange(4))
    assert events.names_since(mark) == ["jit(stray_program)"]
    assert events.since(mark)["programs"] == 1


@pytest.mark.slow
def test_chip_smoke_rehearsal_passes_on_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    # stdout is two lines: the report, then the verdict with exactly
    # the keys the chip check reads
    report_line, verdict_line = proc.stdout.strip().splitlines()
    result, verdict = json.loads(report_line), json.loads(verdict_line)
    assert result["ok"] and result["rehearsal"] and result["codec_path"] == "native"
    assert result["platform"] == "cpu" and all(result["checks"].values())
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int


def test_kernel_result_checks_hold_in_interpret_mode():
    from chip_checks import run_kernel_checks

    report = run_kernel_checks(
        num_docs=64, unit_capacity=512, rle_entries=256, num_slots=8,
        sparse_width=16, seed=3, interpret=True,
    )
    checks = {name: entry for name, entry in report.items() if isinstance(entry, dict)}
    assert set(checks) == {
        "unit_dense_pallas", "rle_dense_pallas", "rle_sparse_pallas",
        "rle_catchup_pack", "rle_compact", "rle_append",
    }
    assert all(entry["ok"] for entry in checks.values()), report
    # the stream really conflicts and deletes: the check is not vacuous
    assert checks["unit_dense_pallas"]["tombstones"] > 0
    assert checks["rle_catchup_pack"]["tombstone_entries"] > 0
    assert checks["rle_compact"]["entries_after"] <= checks["rle_compact"]["entries_before"]


def test_kernel_result_check_notices_a_wrong_kernel(monkeypatch):
    """The check bites: a Pallas path that drops deletes is reported."""
    import hocuspocus_tpu.tpu.pallas_kernels as pk
    from chip_checks import run_kernel_checks
    from hocuspocus_tpu.tpu.kernels import KIND_DELETE, KIND_NOOP, integrate_op_slots

    def lossy(state, ops, *, interpret=False):
        import jax.numpy as jnp

        kept = ops._replace(kind=jnp.where(ops.kind == KIND_DELETE, KIND_NOOP, ops.kind))
        state, _count = integrate_op_slots(state, kept)
        return state, jnp.sum(ops.kind != KIND_NOOP)

    monkeypatch.setattr(pk, "integrate_op_slots_pallas", lossy)
    report = run_kernel_checks(
        num_docs=64, unit_capacity=512, rle_entries=256, num_slots=8,
        sparse_width=16, seed=3, interpret=True,
    )
    assert report["unit_dense_pallas"]["ok"] is False
    assert report["unit_dense_pallas"]["differ"] == ["deleted"]
    assert report["rle_dense_pallas"]["ok"] is True
