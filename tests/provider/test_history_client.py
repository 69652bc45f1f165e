"""HistoryClient + awareness-cursor helpers: the client-side DX layer
over the History extension and relative positions."""

import pytest

from hocuspocus_tpu.extensions import History
from hocuspocus_tpu.provider import HistoryClient, HistoryError

from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced


def _assert(cond):
    assert cond


async def test_history_client_full_flow():
    server = await new_hocuspocus(extensions=[History()])
    writer = new_provider(server, name="hc-doc")
    reviewer = new_provider(server, name="hc-doc")
    history = HistoryClient(reviewer)
    try:
        await wait_synced(writer, reviewer)
        text = writer.document.get_text("t")
        text.insert(0, "checkpoint me")
        await retryable_assertion(
            lambda: _assert(reviewer.document.get_text("t").to_string() == "checkpoint me")
        )

        version = await history.checkpoint("v1")
        assert version["label"] == "v1"

        text.insert(0, "NEW: ")
        await retryable_assertion(
            lambda: _assert(
                reviewer.document.get_text("t").to_string() == "NEW: checkpoint me"
            )
        )

        versions = await history.list()
        assert [v["label"] for v in versions] == ["v1"]

        old = await history.preview(version["id"])
        assert old.get_text("t").to_string() == "checkpoint me"

        delta = await history.diff(version["id"], root="t")
        added = [
            op["insert"]
            for op in delta
            if op.get("attributes", {}).get("ychange", {}).get("type") == "added"
        ]
        assert added == ["NEW: "]

        await history.restore(version["id"])
        await retryable_assertion(
            lambda: _assert(
                writer.document.get_text("t").to_string() == "checkpoint me"
            )
        )

        with pytest.raises(HistoryError):
            await history.preview(99999)
    finally:
        history.destroy()
        writer.destroy()
        reviewer.destroy()
        await server.destroy()


async def test_awareness_cursor_helpers_roundtrip():
    server = await new_hocuspocus()
    a = new_provider(server, name="cursor-doc")
    b = new_provider(server, name="cursor-doc")
    try:
        await wait_synced(a, b)
        ta = a.document.get_text("t")
        ta.insert(0, "the quick brown fox")
        await retryable_assertion(
            lambda: _assert(
                b.document.get_text("t").to_string() == "the quick brown fox"
            )
        )

        # A selects "quick" (4..9); B resolves it against ITS doc copy
        a.set_awareness_cursor(ta, 4, 9)

        def _b_sees_cursor():
            states = b.awareness.get_states()
            state = states.get(a.document.client_id)
            _assert(state is not None and "cursor" in state)
            resolved = b.resolve_awareness_cursor(state["cursor"], b.document)
            _assert(resolved == {"anchor": 4, "head": 9})

        await retryable_assertion(_b_sees_cursor)

        # concurrent edits shift the selection but not its TARGET text
        b.document.get_text("t").insert(0, ">>> ")
        await retryable_assertion(
            lambda: _assert(ta.to_string().startswith(">>> "))
        )
        state = b.awareness.get_states()[a.document.client_id]
        resolved = b.resolve_awareness_cursor(state["cursor"], b.document)
        assert resolved == {"anchor": 8, "head": 13}
        text = b.document.get_text("t").to_string()
        assert text[resolved["anchor"]:resolved["head"]] == "quick"

        # malformed fields resolve to None, never raise
        assert b.resolve_awareness_cursor("junk", b.document) is None
        assert b.resolve_awareness_cursor({"anchor": "zz"}, b.document) is None
    finally:
        a.destroy()
        b.destroy()
        await server.destroy()


async def test_history_client_rid_correlation_is_exact():
    """Regression (round-5 review): errors were routed to the OLDEST pending
    future and broadcasts matched by kind alone, so another client's
    concurrent checkpoint/restore could resolve (or an error reject)
    the wrong awaitable. The rid echo makes correlation exact."""
    import asyncio
    import json as _json

    from hocuspocus_tpu.provider.history import HistoryClient, HistoryError

    class FakeProvider:
        def __init__(self):
            self.sent = []
            self.handlers = []

        def on(self, event, handler):
            self.handlers.append(handler)

        def off(self, event, handler):
            self.handlers.remove(handler)

        def send_stateless(self, payload):
            self.sent.append(_json.loads(payload))

        def deliver(self, event: dict):
            for handler in list(self.handlers):
                handler({"payload": _json.dumps(event)})

    provider = FakeProvider()
    client = HistoryClient(provider, timeout=5.0)

    checkpoint_task = asyncio.ensure_future(client.checkpoint("mine"))
    preview_task = asyncio.ensure_future(client.preview(123))
    await asyncio.sleep(0)  # let both requests register + send
    assert len(provider.sent) == 2
    checkpoint_rid = provider.sent[0]["rid"]
    preview_rid = provider.sent[1]["rid"]
    assert checkpoint_rid and preview_rid and checkpoint_rid != preview_rid

    # ANOTHER client's broadcast (foreign rid) must not resolve ours
    provider.deliver(
        {"event": "history.checkpointed", "id": 99, "label": "theirs",
         "ts": 1.0, "rid": "someone-else-7"}
    )
    await asyncio.sleep(0)
    assert not checkpoint_task.done()

    # the error for the PREVIEW must reject the preview future, not the
    # oldest pending one (the checkpoint)
    provider.deliver(
        {"event": "history.error", "error": "unknown version", "rid": preview_rid}
    )
    await asyncio.sleep(0)
    assert not checkpoint_task.done()
    try:
        await preview_task
        raise AssertionError("preview should have raised HistoryError")
    except HistoryError as error:
        assert "unknown version" in str(error)

    # our own broadcast (our rid) resolves our checkpoint with OUR id
    provider.deliver(
        {"event": "history.checkpointed", "id": 2, "label": "mine",
         "ts": 2.0, "rid": checkpoint_rid}
    )
    version = await checkpoint_task
    assert version["id"] == 2 and version["label"] == "mine"

    # a store-minted broadcast (rid-less, origin "store") must NOT
    # resolve a pending rid-bearing checkpoint via the legacy fallback
    checkpoint_task2 = asyncio.ensure_future(client.checkpoint("mine-2"))
    await asyncio.sleep(0)
    rid2 = provider.sent[-1]["rid"]
    provider.deliver(
        {"event": "history.checkpointed", "id": 7, "label": "store",
         "ts": 3.0, "origin": "store"}
    )
    await asyncio.sleep(0)
    assert not checkpoint_task2.done(), (
        "store-minted broadcast must not satisfy a pending request"
    )
    provider.deliver(
        {"event": "history.checkpointed", "id": 3, "label": "mine-2",
         "ts": 4.0, "rid": rid2}
    )
    assert (await checkpoint_task2)["id"] == 3

    # rid-less events (legacy server) still resolve by kind in send order
    list_task = asyncio.ensure_future(client.list())
    await asyncio.sleep(0)
    provider.deliver({"event": "history.versions", "versions": [{"id": 1}]})
    assert await list_task == [{"id": 1}]

    client.destroy()
