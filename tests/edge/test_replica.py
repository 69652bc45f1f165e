"""Hot-doc scale-out (ISSUE 16): follower cells + read-replica fan-out.

Owner-vs-follower byte-identical convergence under concurrent writes,
mid-stream follower join, lost-REPLICA_TICK gap resync (loud, never
silent), owner-death promotion with zero acked-update loss, and the
below-watermark no-op guarantee (routing byte-identical to PR-13)."""

import asyncio

from hocuspocus_tpu.crdt import encode_state_as_update
from hocuspocus_tpu.observability.flight_recorder import get_flight_recorder

from tests.edge.test_edge_e2e import Topology
from tests.utils import wait_for, wait_synced


def _cell_ext(topo, cell_id):
    return next(ext for _, ext in topo.cells if ext.cell_id == cell_id)


def _cell_server(topo, cell_id):
    return next(server for server, ext in topo.cells if ext.cell_id == cell_id)


def _cell_doc(topo, cell_id, name):
    return _cell_server(topo, cell_id).hocuspocus.documents.get(name)


async def _grow_audience(topo, edge_index, name, count):
    readers = [topo.provider(edge_index, name) for _ in range(count)]
    await wait_synced(*readers, timeout=30)
    return readers


async def test_below_watermark_stays_single_owner():
    """Audience below the watermark: replica routing is byte-identical
    to the single-owner PR-13 path — no hints, no followers, route_set
    collapses to [owner]."""
    topo = await Topology().start(cells=3, edges=2, replica_watermark=64)
    try:
        writer = topo.provider(0, "calm-doc")
        reader = topo.provider(1, "calm-doc")
        await wait_synced(writer, reader)
        for _, gx in topo.edges:
            assert gx.gateway.counters["follow_hints"] == 0
            assert gx.gateway.replica_route_set("calm-doc") == [
                gx.gateway.router.route("calm-doc")
            ]
        for _, ext in topo.cells:
            assert not ext.replicas.owned and not ext.replicas.following
    finally:
        await topo.close()


async def test_owner_vs_follower_byte_identical_convergence_fuzz():
    """The core guarantee: a watermark-crossing audience grows follower
    cells whose local Documents converge BYTE-IDENTICALLY with the
    owner's under concurrent writes — catch-up and fan-out served from
    a follower carry exactly the owner's state."""
    topo = await Topology().start(cells=3, edges=2, replica_watermark=2)
    try:
        writer = topo.provider(0, "viral")
        await wait_synced(writer)
        # pre-audience history: the follower bootstrap must carry it
        writer.document.get_text("body").insert(0, "pre-viral-history ")
        readers = await _grow_audience(topo, 1, "viral", 5)
        gateway = topo.edges[1][1].gateway
        owner_id = gateway.router.route("viral")
        owner_ext = _cell_ext(topo, owner_id)
        # audience 5 over watermark 2 wants 2 followers (cap healthy-1)
        await wait_for(
            lambda: len(
                (owner_ext.replicas.owned.get("viral") or {"followers": {}})[
                    "followers"
                ]
            )
            == 2,
            timeout=15,
        )
        follower_ids = sorted(owner_ext.replicas.owned["viral"]["followers"])
        # followers finish the bootstrap exchange (synced, not resyncing)
        await wait_for(
            lambda: all(
                _cell_ext(topo, f).replicas.following.get("viral", {}).get("synced")
                for f in follower_ids
            ),
            timeout=15,
        )
        # concurrent write fuzz at the owner + a reader echoing back
        for round_no in range(4):
            text = writer.document.get_text("body")
            text.insert(len(text), f"w{round_no} ")
            rtext = readers[0].document.get_text("body")
            rtext.insert(0, f"r{round_no} ")
            await asyncio.sleep(0.03)
        owner_doc = _cell_doc(topo, owner_id, "viral")
        for follower_id in follower_ids:
            await wait_for(
                lambda f=follower_id: encode_state_as_update(
                    _cell_doc(topo, f, "viral")
                )
                == encode_state_as_update(owner_doc),
                timeout=15,
            )
        # every client converged through whatever replica served it
        for provider in [writer] + readers:
            await wait_for(
                lambda p=provider: encode_state_as_update(p.document)
                == encode_state_as_update(owner_doc),
                timeout=15,
            )
        # the owner streamed coalesced ticks; followers applied them
        assert owner_ext.replicas.counters["ticks_out"] > 0
        assert sum(
            _cell_ext(topo, f).replicas.counters["ticks_in"]
            for f in follower_ids
        ) > 0
        # the audience spread: not every reader channel rides the owner
        route_set = gateway.replica_route_set("viral")
        assert len(route_set) == 3
    finally:
        await topo.close()


async def test_midstream_follower_join_converges():
    """A follower that joins MID-STREAM — after ticks already flowed —
    bootstraps the full history via the snapshot/SV-diff reply and then
    rides the live tick stream."""
    topo = await Topology().start(cells=4, edges=2, replica_watermark=2)
    try:
        writer = topo.provider(0, "viral")
        await wait_synced(writer)
        readers = await _grow_audience(topo, 1, "viral", 4)
        gateway = topo.edges[1][1].gateway
        owner_id = gateway.router.route("viral")
        owner_ext = _cell_ext(topo, owner_id)
        await wait_for(
            lambda: len(
                (owner_ext.replicas.owned.get("viral") or {"followers": {}})[
                    "followers"
                ]
            )
            >= 2,
            timeout=15,
        )
        first_wave = set(owner_ext.replicas.owned["viral"]["followers"])
        # live ticks flow before the late follower exists
        for i in range(3):
            text = writer.document.get_text("body")
            text.insert(len(text), f"early-{i} ")
            await asyncio.sleep(0.03)
        await wait_for(lambda: owner_ext.replicas.counters["ticks_out"] >= 1)
        # audience doubles: a THIRD follower stands up mid-stream
        readers += await _grow_audience(topo, 1, "viral", 4)
        await wait_for(
            lambda: len(owner_ext.replicas.owned["viral"]["followers"]) == 3,
            timeout=15,
        )
        late = set(owner_ext.replicas.owned["viral"]["followers"]) - first_wave
        assert len(late) == 1
        late_id = late.pop()
        writer.document.get_text("body").insert(0, "after-join ")
        owner_doc = _cell_doc(topo, owner_id, "viral")
        # the owner first (the writer's edit is still on its way to it, and
        # follower == owner holds before it lands, too), then the follower
        await wait_for(
            lambda: "after-join" in str(owner_doc.get_text("body"))
            and _cell_doc(topo, late_id, "viral") is not None
            and encode_state_as_update(_cell_doc(topo, late_id, "viral"))
            == encode_state_as_update(owner_doc),
            timeout=15,
        )
        late_text = str(_cell_doc(topo, late_id, "viral").get_text("body"))
        assert "early-0" in late_text and "after-join" in late_text
    finally:
        await topo.close()


async def test_lost_tick_heals_via_resync_never_silently():
    """A dropped REPLICA_TICK leaves a seq gap: the follower counts a
    resync, records a __replica__ lag_resync event, re-FOLLOWs with its
    state vector and converges — loss is loud and healed, never
    silent."""
    topo = await Topology().start(cells=3, edges=2, replica_watermark=2)
    try:
        writer = topo.provider(0, "viral")
        await wait_synced(writer)
        await _grow_audience(topo, 1, "viral", 5)
        gateway = topo.edges[1][1].gateway
        owner_id = gateway.router.route("viral")
        owner_ext = _cell_ext(topo, owner_id)
        await wait_for(
            lambda: len(
                (owner_ext.replicas.owned.get("viral") or {"followers": {}})[
                    "followers"
                ]
            )
            >= 1,
            timeout=15,
        )
        follower_ids = sorted(owner_ext.replicas.owned["viral"]["followers"])
        await wait_for(
            lambda: all(
                _cell_ext(topo, f).replicas.following.get("viral", {}).get("synced")
                for f in follower_ids
            ),
            timeout=15,
        )
        resyncs_before = sum(
            _cell_ext(topo, f).replicas.counters["resyncs"] for f in follower_ids
        )
        # simulate the lost envelope: the owner's next tick skips a seq
        owner_ext.replicas.owned["viral"]["seq"] += 1
        text = writer.document.get_text("body")
        text.insert(len(text), "post-gap ")
        await wait_for(
            lambda: sum(
                _cell_ext(topo, f).replicas.counters["resyncs"]
                for f in follower_ids
            )
            > resyncs_before,
            timeout=15,
        )
        # the resync reply re-syncs the follower and state converges
        owner_doc = _cell_doc(topo, owner_id, "viral")
        for follower_id in follower_ids:
            await wait_for(
                lambda f=follower_id: _cell_ext(topo, f)
                .replicas.following["viral"]["synced"]
                and encode_state_as_update(_cell_doc(topo, f, "viral"))
                == encode_state_as_update(owner_doc),
                timeout=15,
            )
        events = [
            event
            for event in get_flight_recorder().events("__replica__")
            if event.get("event") == "lag_resync"
        ]
        assert events, "gap resync must land in the __replica__ ring"
    finally:
        await topo.close()


async def test_owner_death_promotes_freshest_follower_zero_loss():
    """Owner drain under live traffic: the edge promotes a surviving
    follower (router entries cleared, epoch bumped), the promoted cell
    flips role in place, and nothing acknowledged is lost — no
    client-visible disconnect, byte-identical convergence after."""
    topo = await Topology().start(cells=3, edges=2, replica_watermark=2)
    try:
        writer = topo.provider(0, "viral")
        await wait_synced(writer)
        readers = await _grow_audience(topo, 1, "viral", 5)
        gateways = [gx.gateway for _, gx in topo.edges]
        owner_id = gateways[0].router.route("viral")
        owner_ext = _cell_ext(topo, owner_id)
        await wait_for(
            lambda: len(
                (owner_ext.replicas.owned.get("viral") or {"followers": {}})[
                    "followers"
                ]
            )
            == 2,
            timeout=15,
        )
        follower_ids = sorted(owner_ext.replicas.owned["viral"]["followers"])
        await wait_for(
            lambda: all(
                _cell_ext(topo, f).replicas.following.get("viral", {}).get("synced")
                for f in follower_ids
            ),
            timeout=15,
        )
        # acked history the promotion must not lose
        writer.document.get_text("body").insert(0, "acked-pre-promotion ")
        await wait_for(
            lambda: "acked-pre-promotion"
            in str(readers[0].document.get_text("body"))
        )
        closes = []
        for provider in [writer] + readers:
            provider.on("close", lambda *a, **k: closes.append("close"))
        await _cell_server(topo, owner_id).drain(timeout_secs=5)
        # the edge promoted a follower and cleared the stale route
        await wait_for(
            lambda: all(
                g.router.route("viral") in follower_ids for g in gateways
            ),
            timeout=15,
        )
        new_owner = gateways[0].router.route("viral")
        assert any(g.counters["promotions"] >= 1 for g in gateways)
        new_ext = _cell_ext(topo, new_owner)
        await wait_for(
            lambda: "viral" in new_ext.replicas.owned
            and "viral" not in new_ext.replicas.following,
            timeout=15,
        )
        assert new_ext.replicas.counters["promotions"] >= 1
        # concurrent edits keep flowing through the promoted owner
        writer.document.get_text("body").insert(0, "post-promotion ")
        await wait_for(
            lambda: "post-promotion" in str(readers[0].document.get_text("body")),
            timeout=20,
        )
        for provider in [writer] + readers:
            await wait_for(
                lambda p=provider: encode_state_as_update(p.document)
                == encode_state_as_update(writer.document),
                timeout=20,
            )
        body = str(readers[-1].document.get_text("body"))
        assert "acked-pre-promotion" in body and "post-promotion" in body
        assert not closes, f"client-visible disconnect during promotion: {closes}"
    finally:
        await topo.close()


async def test_replica_metrics_and_fleet_rollup_surface():
    """Observability satellite: hocuspocus_replica_* counters move,
    stats() lands in the cell digest shape /debug/fleet rolls up, and
    the gateway status exposes the per-doc replica table."""
    topo = await Topology().start(cells=3, edges=1, replica_watermark=2)
    try:
        writer = topo.provider(0, "viral")
        await wait_synced(writer)
        await _grow_audience(topo, 0, "viral", 5)
        gateway = topo.edges[0][1].gateway
        owner_id = gateway.router.route("viral")
        owner_ext = _cell_ext(topo, owner_id)
        await wait_for(
            lambda: len(
                (owner_ext.replicas.owned.get("viral") or {"followers": {}})[
                    "followers"
                ]
            )
            == 2,
            timeout=15,
        )
        followers_gauge = owner_ext.replicas.metrics()[0]
        assert followers_gauge.name == "hocuspocus_replica_followers"
        assert followers_gauge.value() == 2.0
        stats = owner_ext.replicas.stats()
        assert stats["owned"]["viral"]["followers"]
        assert stats["counters"]["bootstraps"] >= 2
        status = gateway.status()
        table = status["replica"]["docs"].get("viral")
        assert table is not None and len(table["followers"]) == 2
        assert table["owner"] == owner_id
        follower_id = sorted(owner_ext.replicas.owned["viral"]["followers"])[0]
        follower_stats = _cell_ext(topo, follower_id).replicas.stats()
        assert follower_stats["following"]["viral"]["owner"] == owner_id
        assert "lag_s" in follower_stats["following"]["viral"]
    finally:
        await topo.close()
