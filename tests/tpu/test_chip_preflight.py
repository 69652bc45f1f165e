"""AOT pre-flight: compile every device program at chip_smoke.py's shapes
for a TPU v5e — on this host, holding no chip.

libtpu can compile for a described topology without the hardware
(`jax.experimental.topologies`), so a Mosaic rejection or an HBM
refusal at deployment shape is learned here, in seconds, before any
chip time is spent. It says nothing about running, or about results;
`chip_smoke.py` is the proof of those.

Shapes: the 8,192 x 5,632 shard plane's whole warm grid (the one-chip
smoke, 13 of them resident), the 32,768 x 5,632 cell plane's widest
programs (the four-chip smoke), and the RLE arena programs
`chip_checks` runs at 8,192 x 1,024.
"""

from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import pytest

from hocuspocus_tpu.tpu import kernels, kernels_rle, pallas_kernels_rle
from hocuspocus_tpu.tpu.kernels import DocState, OpBatch
from hocuspocus_tpu.tpu.kernels_rle import RleState
from hocuspocus_tpu.tpu.merge_plane import MergePlane

CAPACITY = 5632
RLE_ENTRIES = 1024
PACK_WIDTH = 128
V5E_HBM_BYTES = 15.75e9  # what libtpu reports for "TPU v5 lite"


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2", chips_per_host_bounds=(2, 2, 1)
        )
    except Exception as error:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"libtpu gave no v5e topology to compile for: {error!r}")
    # a pre-flight compiles: it neither trusts an entry an earlier run
    # left in the persistent cache nor leaves chip executables there
    # (the compile-only client could not load them back anyway)
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _unit_state(sds, d, n):
    return DocState(
        sds((d, n), jnp.uint32), sds((d, n), jnp.int32), sds((d, n), jnp.int32),
        sds((d, n), jnp.int32), sds((d, n), jnp.bool_), sds((d,), jnp.int32),
        sds((d,), jnp.bool_),
    )


def _rle_state(sds, d, r):
    return RleState(
        sds((d, r), jnp.uint32), sds((d, r), jnp.int32), sds((d, r), jnp.int32),
        sds((d, r), jnp.int32), sds((d, r), jnp.int32), sds((d, r), jnp.bool_),
        sds((d,), jnp.int32), sds((d,), jnp.int32), sds((d,), jnp.bool_),
    )


def _ops(sds, k, b):
    u, i = sds((k, b), jnp.uint32), sds((k, b), jnp.int32)
    return OpBatch(i, u, i, i, u, i, u, i)


def _warm_programs(sds, plane, entries):
    """(label, lowered) for warm-grid `entries` of `plane` at row width
    CAPACITY: the step the plane itself dispatches at each shape —
    Pallas or XLA as its own seams decide on a TPU — lowered from the
    no-op arguments its warm pass runs, with the arena donated as the
    live steps donate it."""
    state = _unit_state(sds, plane.num_docs, CAPACITY)
    for entry in entries:
        site, shape_key = plane._warm_site(entry)
        step, args = plane._warm_program(entry)
        specs = jax.tree.map(lambda a: sds(a.shape, a.dtype), args)
        # the health probe only reads; every other step returns the arena
        donate = () if site == "health_probe" else (0,)
        label = f"{site} {'x'.join(map(str, shape_key))}"
        yield label, jax.jit(step, donate_argnums=donate).lower(state, *specs)


def _extra_unit_programs(sds, num_docs, pack_widths=(), compact=None, scan=None):
    """Unit-arena programs outside MergePlane's warm grid: the serving
    catch-up pack per gather width, the compaction step, and the plain
    scan chip_checks uses as the dense sweep's reference."""
    state = _unit_state(sds, num_docs, CAPACITY)
    for w in pack_widths:
        yield f"catchup_pack {w}", kernels.catchup_pack.lower(
            state, sds((w,), jnp.int32), PACK_WIDTH
        )
    if compact:
        yield f"compact {compact}", kernels.compact_doc_rows.lower(
            state, sds((compact,), jnp.int32)
        )
    if scan:
        yield f"dense scan {scan}x{num_docs}", kernels.integrate_op_slots.lower(
            state, _ops(sds, scan, num_docs)
        )


def _rle_programs(sds, num_docs, k, b):
    state = _rle_state(sds, num_docs, RLE_ENTRIES)
    slots = sds((b,), jnp.int32)
    run = (sds((k, b), jnp.uint32), sds((k, b), jnp.int32), sds((k, b), jnp.int32))
    yield "rle dense pallas", pallas_kernels_rle._integrate_pallas_rle.lower(
        state, _ops(sds, k, num_docs), False
    )
    yield "rle sparse pallas", pallas_kernels_rle._integrate_sparse_pallas_rle.lower(
        state, _ops(sds, k, b), slots, False
    )
    yield "rle dense scan", kernels_rle.integrate_op_slots_rle.lower(
        state, _ops(sds, k, num_docs)
    )
    yield "rle sparse scan", kernels_rle.integrate_op_slots_rle_sparse.lower(
        state, _ops(sds, k, b), slots
    )
    yield "rle append", kernels_rle.append_run_slots_rle_sparse.lower(state, *run, slots)
    yield "rle compact", kernels_rle.compact_doc_rows_rle.lower(state, slots)
    yield "rle catchup_pack", kernels_rle.catchup_pack_rle.lower(state, slots, PACK_WIDTH)
    yield "rle health_probe", kernels_rle.health_probe_rle.lower(state, slots)


@pytest.fixture
def on_a_tpu(monkeypatch):
    """The plane's step seams pick Pallas by `jax.default_backend()`:
    answer as the chip machine does, while lowering for the topology."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_every_smoke_program_compiles_for_v5e(v5e, on_a_tpu):
    from hocuspocus_tpu.tpu.serving import PlaneServing

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    # grid and step selection come from the plane itself (its own row
    # width is irrelevant to either: the lowered arena is CAPACITY wide)
    shard = MergePlane(num_docs=8192, capacity=8)
    cell = MergePlane(num_docs=32768, capacity=8)
    k_max = cell._k_buckets()[-1]
    cell_grid = [
        (k_max, 32768),
        (k_max, cell._b_buckets()[-1]),
        ("append", k_max, 32768),
    ]
    # (label, lowered, bytes resident on the chip beside this program's own arena)
    arena = 8192 * CAPACITY * 17
    shard_programs = list(
        _warm_programs(sds, shard, shard.warmup_shapes() + shard.warmup_aux_shapes())
    ) + list(_extra_unit_programs(sds, 8192, (16, 64, 256), compact=64, scan=16))
    # on a TPU the plane sends every Pallas-eligible width to Mosaic
    assert "tpu_custom_call" in dict(shard_programs)["integrate_sparse 16x64"].as_text()
    lowered = [(f"shard {label}", low, 12 * arena) for label, low in shard_programs]
    lowered += [
        (f"cell {label}", low, 0)
        for label, low in list(_warm_programs(sds, cell, cell_grid))
        + list(_extra_unit_programs(sds, 32768, (256,)))
    ]
    lowered += [(label, low, 0) for label, low in _rle_programs(sds, 8192, k_max, 64)]
    lowered += [
        (
            f"sv_diff {w}",
            kernels.state_vector_diff.lower(sds((w,), jnp.int32), sds((w,), jnp.int32)),
            0,
        )
        for w in PlaneServing._TRIAGE_WIDTHS
    ]
    assert len(lowered) >= 40

    def compile_one(item):
        label, low, resident = item
        try:
            memory = low.compile().memory_analysis()
        except Exception as error:
            return f"{label}: {type(error).__name__}: {str(error)[:300]}"
        need = (
            resident
            + memory.argument_size_in_bytes
            + memory.output_size_in_bytes
            - memory.alias_size_in_bytes
            + memory.temp_size_in_bytes
        )
        if need > V5E_HBM_BYTES:
            return f"{label}: needs {need / 1e9:.2f} GB of {V5E_HBM_BYTES / 1e9:.2f} GB HBM"
        return None

    # compiles run outside the GIL: measured 123 s in a loop, 70 s pooled
    with ThreadPoolExecutor(max_workers=8) as pool:
        refused = [verdict for verdict in pool.map(compile_one, lowered) if verdict]
    assert not refused, "\n".join(refused)


@pytest.mark.slow  # ~20 s of compiling towards a refusal: not worth every tier-1 run
def test_single_100k_plane_warm_grid_does_not_fit_a_v5e(v5e, on_a_tpu):
    """The BASELINE regime as ONE plane cannot compile its own warm
    grid on a 16 GB chip (ROADMAP S2/D3's input; seen on the chip in
    PR 21): the run-append entry at B = num_docs gathers a copy of the
    whole 9.6 GB arena. A design change that makes it fit shows up as
    this test failing."""

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    plane = MergePlane(num_docs=100_000, capacity=8)
    ((_, low),) = _warm_programs(sds, plane, [("append", 16, 100_000)])
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|Ran out of memory|exceeds"):
        low.compile()
