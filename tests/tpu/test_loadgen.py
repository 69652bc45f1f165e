"""Served-load harness (hocuspocus_tpu.loadgen) at CI scale.

The harness the scenario runner builds on — here with
small populations so CI proves the topology end-to-end: sockets-free
providers, sharded serve planes, background load, cross-instance Redis
fan-out (verdict item: "measure the SERVED 100k regime without
sockets").
"""

import pytest

from hocuspocus_tpu.loadgen import run_served_load

# ~70s of served-load topology runs: excluded from the tier-1 gate
# (-m 'not slow'); the full suite still runs wherever slow tests do
pytestmark = pytest.mark.slow


async def test_loadgen_single_instance():
    result = await run_served_load(
        num_docs=96,
        sampled=8,
        edits=12,
        shards=2,
        shard_rows=64,
        capacity=512,
        docs_per_socket=48,
        sync_timeout=60,
        budget_s=120,
    )
    assert result["metric"] == "served_merge_to_broadcast_p99_ms"
    assert result["value"] > 0
    assert result["extra"]["docs"] == 96
    assert result["extra"]["samples"] == 12
    # reproducibility: the harness RNG seed rides in the artifact
    assert result["extra"]["seed"] == 0
    health = result["extra"]["plane_health"][0]
    assert health["plane_broadcasts"] > 0
    assert health["cpu_fallbacks"] == 0
    # every doc landed on a serve plane
    assert result["extra"]["served_docs"][0] == 96


async def test_loadgen_cross_instance_redis():
    result = await run_served_load(
        num_docs=24,
        instances=2,
        sampled=4,
        edits=8,
        shards=2,
        shard_rows=32,
        capacity=512,
        docs_per_socket=24,
        sync_timeout=60,
        budget_s=120,
    )
    assert result["extra"]["cross_instance"] is True
    assert result["extra"]["samples"] == 8
    # the timed path crossed instances: instance 1 (readers) served too
    assert result["extra"]["served_docs"][1] >= 4
    for health in result["extra"]["plane_health"]:
        assert health["cpu_fallbacks"] == 0


async def test_loadgen_scales_population_beyond_fd_budget():
    """A population of sockets this size would exhaust default fd
    limits with real websockets (2 fds per socket end); in-process it
    is just objects. Keeps CI honest about the harness's reason to
    exist without burning minutes (1,024 docs)."""
    result = await run_served_load(
        num_docs=1024,
        sampled=8,
        edits=10,
        shards=4,
        shard_rows=384,
        capacity=256,
        docs_per_socket=256,
        sync_timeout=300,
        budget_s=300,
    )
    assert result["extra"]["served_docs"][0] == 1024
    assert result["extra"]["plane_health"][0]["cpu_fallbacks"] == 0
