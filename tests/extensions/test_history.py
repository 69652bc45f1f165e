"""Version-history extension: checkpoint / list / preview / restore
driven by real providers over the stateless channel."""

import base64
import json

from hocuspocus_tpu.crdt import Doc, apply_update
from hocuspocus_tpu.extensions import History

from tests.utils import new_hocuspocus, new_provider, retryable_assertion, wait_synced


def _assert(cond):
    assert cond


def _collect(provider, into):
    provider.on("stateless", lambda data: into.append(json.loads(data["payload"])))


async def test_checkpoint_list_preview_restore_roundtrip():
    history = History()
    server = await new_hocuspocus(extensions=[history])
    a = new_provider(server, name="versioned")
    b = new_provider(server, name="versioned")
    a_events: list = []
    b_events: list = []
    _collect(a, a_events)
    _collect(b, b_events)
    try:
        await wait_synced(a, b)
        ta = a.document.get_text("t")
        ta.insert(0, "first draft")
        ta.format(0, 5, {"bold": True})
        a.document.get_map("meta").set("stage", "draft")
        await retryable_assertion(
            lambda: _assert(b.document.get_text("t").to_string() == "first draft")
        )

        a.send_stateless(json.dumps({"action": "history.checkpoint", "label": "v1"}))
        # checkpoint broadcasts to EVERY client
        await retryable_assertion(
            lambda: _assert(
                any(e.get("event") == "history.checkpointed" for e in b_events)
            )
        )
        checkpointed = next(e for e in b_events if e["event"] == "history.checkpointed")
        assert checkpointed["label"] == "v1"
        vid = checkpointed["id"]

        # keep editing past the checkpoint
        ta.delete(0, 6)
        ta.insert(0, "second ")
        a.document.get_map("meta").set("stage", "final")
        await retryable_assertion(
            lambda: _assert(
                b.document.get_text("t").to_string() == "second draft"
            )
        )

        # list
        a.send_stateless(json.dumps({"action": "history.list"}))
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.versions" for e in a_events))
        )
        versions = next(e for e in a_events if e["event"] == "history.versions")
        assert [v["id"] for v in versions["versions"]] == [vid]

        # preview: client reconstructs the version from update bytes
        a.send_stateless(json.dumps({"action": "history.preview", "id": vid}))
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.preview" for e in a_events))
        )
        preview = next(e for e in a_events if e["event"] == "history.preview")
        pdoc = Doc()
        apply_update(pdoc, base64.b64decode(preview["update"]), "preview")
        assert pdoc.get_text("t").to_string() == "first draft"
        assert pdoc.get_text("t").to_delta()[0] == {
            "insert": "first",
            "attributes": {"bold": True},
        }
        assert pdoc.get_map("meta").get("stage") == "draft"

        # restore: BOTH live clients converge back to v1, formatting intact
        b.send_stateless(json.dumps({"action": "history.restore", "id": vid}))
        await retryable_assertion(
            lambda: _assert(
                a.document.get_text("t").to_string() == "first draft"
                and b.document.get_text("t").to_string() == "first draft"
                and a.document.get_map("meta").get("stage") == "draft"
            ),
            timeout=15,
        )
        assert a.document.get_text("t").to_delta()[0] == {
            "insert": "first",
            "attributes": {"bold": True},
        }
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.restored" for e in a_events))
        )
    finally:
        a.destroy()
        b.destroy()
        await server.destroy()


async def test_unknown_version_and_action_answer_errors():
    server = await new_hocuspocus(extensions=[History()])
    p = new_provider(server, name="errs")
    events: list = []
    _collect(p, events)
    try:
        await wait_synced(p)
        p.send_stateless(json.dumps({"action": "history.restore", "id": 999}))
        p.send_stateless(json.dumps({"action": "history.bogus"}))
        await retryable_assertion(
            lambda: _assert(
                sum(1 for e in events if e.get("event") == "history.error") >= 2
            )
        )
    finally:
        p.destroy()
        await server.destroy()


async def test_array_roots_restore_and_version_cap():
    history = History(max_versions=2)
    server = await new_hocuspocus(extensions=[history])
    p = new_provider(server, name="arr")
    events: list = []
    _collect(p, events)
    try:
        await wait_synced(p)
        arr = p.document.get_array("items")
        arr.insert(0, [1, 2, 3])
        await retryable_assertion(lambda: _assert(len(history._docs["arr"].archive.get_array("items")) == 3))
        for label in ("one", "two", "three"):  # cap 2: 'one' evicted
            p.send_stateless(json.dumps({"action": "history.checkpoint", "label": label}))
        await retryable_assertion(
            lambda: _assert(
                sum(1 for e in events if e.get("event") == "history.checkpointed") == 3
            )
        )
        ids = [e["id"] for e in events if e.get("event") == "history.checkpointed"]
        p.send_stateless(json.dumps({"action": "history.list"}))
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.versions" for e in events))
        )
        versions = next(e for e in events if e["event"] == "history.versions")
        assert [v["label"] for v in versions["versions"]] == ["two", "three"]

        arr.delete(0, 3)
        arr.insert(0, ["changed"])
        p.send_stateless(json.dumps({"action": "history.restore", "id": ids[-1]}))
        await retryable_assertion(
            lambda: _assert(p.document.get_array("items").to_json() == [1, 2, 3]),
            timeout=15,
        )
    finally:
        p.destroy()
        await server.destroy()


async def test_xml_roots_restore_via_deep_clones():
    """XML trees restore: elements keep attributes and children, text
    keeps its formatted delta — rebuilt as fresh prelim nodes."""
    server = await new_hocuspocus(extensions=[History()])
    p = new_provider(server, name="xmldoc")
    q = new_provider(server, name="xmldoc")
    events: list = []
    _collect(p, events)
    try:
        await wait_synced(p, q)
        from hocuspocus_tpu.crdt import YXmlElement, YXmlText

        frag = p.document.get_xml_fragment("x")
        para = YXmlElement("paragraph")
        frag.push([para])
        para.set_attribute("align", "left")
        t = YXmlText()
        para.push([t])
        t.insert(0, "styled tree")
        t.format(0, 6, {"bold": True})
        await retryable_assertion(
            lambda: _assert("styled" in q.document.get_xml_fragment("x").to_string())
        )
        p.send_stateless(json.dumps({"action": "history.checkpoint"}))
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.checkpointed" for e in events))
        )
        vid = next(e["id"] for e in events if e["event"] == "history.checkpointed")
        before = p.document.get_xml_fragment("x").to_string()

        # mutate the tree, then restore
        t.delete(0, 7)
        para.set_attribute("align", "center")
        frag.push([YXmlElement("hr")])
        await retryable_assertion(
            lambda: _assert("hr" in q.document.get_xml_fragment("x").to_string())
        )
        p.send_stateless(json.dumps({"action": "history.restore", "id": vid}))
        await retryable_assertion(
            lambda: _assert(
                p.document.get_xml_fragment("x").to_string() == before
                and q.document.get_xml_fragment("x").to_string() == before
            ),
            timeout=15,
        )
        restored_el = q.document.get_xml_fragment("x").get(0)
        assert restored_el.get_attribute("align") == "left"
        assert "<bold>" in before  # formatting markup survived the restore
    finally:
        p.destroy()
        q.destroy()
        await server.destroy()


async def test_read_only_connection_cannot_checkpoint_or_restore():
    """Stateless messages reach hooks regardless of permissions — the
    extension itself must refuse writes from read-only connections."""

    async def on_authenticate(data):
        data.connection_config.read_only = True

    server = await new_hocuspocus(
        extensions=[History()], on_authenticate=on_authenticate
    )
    p = new_provider(server, name="ro", token="t")
    events: list = []
    _collect(p, events)
    try:
        await wait_synced(p)
        p.send_stateless(json.dumps({"action": "history.checkpoint"}))
        p.send_stateless(json.dumps({"action": "history.restore", "id": 1}))
        await retryable_assertion(
            lambda: _assert(
                sum(
                    1
                    for e in events
                    if e.get("event") == "history.error"
                    and "read-only" in e.get("error", "")
                )
                == 2
            )
        )
        # reads still work
        p.send_stateless(json.dumps({"action": "history.list"}))
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.versions" for e in events))
        )
    finally:
        p.destroy()
        await server.destroy()


async def test_unload_and_reload_with_history_installed():
    """The unload payload carries only the document name; the extension
    must detach its update listener from the reference captured at load
    (and a reloaded doc starts a fresh archive)."""
    history = History()
    server = await new_hocuspocus(extensions=[history], debounce=10)
    p = new_provider(server, name="transient")
    try:
        await wait_synced(p)
        p.document.get_text("t").insert(0, "before unload")
        await retryable_assertion(
            lambda: _assert(
                history._docs["transient"].archive.get_text("t").to_string()
                == "before unload"
            )
        )
    finally:
        p.destroy()
    # unload happens after the last connection drops
    await retryable_assertion(
        lambda: _assert("transient" not in server.documents)
    )
    assert "transient" not in history._docs

    # reconnect: fresh archive seeded from whatever persisted/loaded
    q = new_provider(server, name="transient")
    try:
        await wait_synced(q)
        await retryable_assertion(lambda: _assert("transient" in history._docs))
    finally:
        q.destroy()
        await server.destroy()


async def test_history_diff_attributes_authors():
    """history.diff renders an attributed version diff: ychange
    added/removed runs carry user names when the doc replicates a
    PermanentUserData registry."""
    from hocuspocus_tpu.crdt import PermanentUserData

    server = await new_hocuspocus(extensions=[History()])
    alice = new_provider(server, name="attributed")
    bob = new_provider(server, name="attributed")
    events: list = []
    _collect(alice, events)
    try:
        await wait_synced(alice, bob)
        pud_a = PermanentUserData(alice.document)
        pud_b = PermanentUserData(bob.document)
        pud_a.set_user_mapping(alice.document, alice.document.client_id, "alice")
        pud_b.set_user_mapping(bob.document, bob.document.client_id, "bob")

        ta = alice.document.get_text("t")
        ta.insert(0, "alice wrote everything")
        await retryable_assertion(
            lambda: _assert(
                bob.document.get_text("t").to_string() == "alice wrote everything"
            )
        )
        alice.send_stateless(json.dumps({"action": "history.checkpoint", "label": "base"}))
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.checkpointed" for e in events))
        )
        vid = next(e["id"] for e in events if e["event"] == "history.checkpointed")

        # bob removes alice's words and adds his own
        tb = bob.document.get_text("t")
        tb.delete(0, 6)
        tb.insert(0, "bob says: ")
        await retryable_assertion(
            lambda: _assert(
                alice.document.get_text("t").to_string()
                == "bob says: wrote everything"
            )
        )

        alice.send_stateless(
            json.dumps({"action": "history.diff", "id": vid, "root": "t"})
        )
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.diff" for e in events)),
            timeout=15,
        )
        delta = next(e for e in events if e["event"] == "history.diff")["delta"]
        marks = {
            (op["attributes"]["ychange"]["type"], op["attributes"]["ychange"].get("user")): op["insert"]
            for op in delta
            if "attributes" in op and "ychange" in op["attributes"]
        }
        assert marks.get(("added", "bob")) == "bob says: ", delta
        assert marks.get(("removed", "bob")) == "alice ", delta
    finally:
        alice.destroy()
        bob.destroy()
        await server.destroy()


async def test_history_on_plane_served_docs():
    """History must compose with the TPU serve plane: the archive feeds
    from update events regardless of serving mode, and a restore's
    delete-everything-reinsert transaction flows through the plane (or
    degrades it cleanly) without losing data."""
    from hocuspocus_tpu.tpu import TpuMergeExtension

    ext = TpuMergeExtension(num_docs=8, capacity=2048, flush_interval_ms=1, serve=True)
    server = await new_hocuspocus(extensions=[History(), ext])
    a = new_provider(server, name="plane-hist")
    b = new_provider(server, name="plane-hist")
    events: list = []
    _collect(a, events)
    try:
        await wait_synced(a, b)
        ta = a.document.get_text("t")
        ta.insert(0, "plane-served history")
        await retryable_assertion(
            lambda: _assert(
                b.document.get_text("t").to_string() == "plane-served history"
            )
        )
        assert "plane-hist" in ext._docs  # actually plane-served

        a.send_stateless(json.dumps({"action": "history.checkpoint", "label": "v1"}))
        await retryable_assertion(
            lambda: _assert(any(e.get("event") == "history.checkpointed" for e in events))
        )
        vid = next(e["id"] for e in events if e["event"] == "history.checkpointed")

        ta.delete(0, 13)
        ta.insert(0, "rewritten ")
        await retryable_assertion(
            lambda: _assert(
                b.document.get_text("t").to_string() == "rewritten history"
            )
        )

        a.send_stateless(json.dumps({"action": "history.restore", "id": vid}))
        await retryable_assertion(
            lambda: _assert(
                a.document.get_text("t").to_string() == "plane-served history"
                and b.document.get_text("t").to_string() == "plane-served history"
            ),
            timeout=20,
        )
        # steady state continues (whether still plane-served or cleanly
        # degraded, both sides keep converging)
        ta.insert(0, "after; ")
        await retryable_assertion(
            lambda: _assert(
                b.document.get_text("t").to_string() == "after; plane-served history"
            )
        )
    finally:
        a.destroy()
        b.destroy()
        await server.destroy()


async def test_restore_with_all_tombstoned_array_root_is_not_half_rewritten():
    """Regression (round-5 review): an array root EMPTIED before the
    checkpoint carries only tombstones in the (gc-enabled) restored
    doc, and the old classifier defaulted it to 'text' — run() then
    called get_text() on the live YArray root and raised TypeError
    mid-transaction, committing a half-rewrite of the earlier roots.
    The classifier now consults the live root's concrete type, so the
    restore completes cleanly for every root."""
    history = History()
    server = await new_hocuspocus(extensions=[history])
    p = new_provider(server, name="tombstoned-root")
    events: list = []
    _collect(p, events)
    try:
        await wait_synced(p)
        # "aa-text" sorts before "zz-emptied": the text root is
        # rewritten FIRST, so a mis-typed array root would previously
        # abort AFTER the text root was already mutated (half-rewrite)
        arr = p.document.get_array("zz-emptied")
        arr.insert(0, ["gone", "soon"])
        arr.delete(0, 2)  # all-tombstoned at checkpoint time
        text = p.document.get_text("aa-text")
        text.insert(0, "keep me")
        await retryable_assertion(
            lambda: _assert(
                history._docs["tombstoned-root"]
                .archive.get_text("aa-text")
                .to_string()
                == "keep me"
            )
        )
        p.send_stateless(json.dumps({"action": "history.checkpoint", "label": "v"}))
        await retryable_assertion(
            lambda: _assert(
                any(e.get("event") == "history.checkpointed" for e in events)
            )
        )
        vid = next(e for e in events if e["event"] == "history.checkpointed")["id"]

        # diverge both roots, then restore
        text.delete(0, len("keep me"))
        text.insert(0, "overwritten")
        arr.insert(0, ["revived"])
        p.send_stateless(json.dumps({"action": "history.restore", "id": vid}))
        await retryable_assertion(
            lambda: _assert(
                any(e.get("event") == "history.restored" for e in events)
            ),
            timeout=15,
        )
        assert not any(e.get("event") == "history.error" for e in events), events
        assert p.document.get_text("aa-text").to_string() == "keep me"
        assert p.document.get_array("zz-emptied").to_json() == []
    finally:
        p.destroy()
        await server.destroy()


async def test_store_minted_checkpoint_broadcasts_checkpointed():
    """Regression (round-5 review): checkpoint_on_store minted versions
    silently — clients only discovered them by polling history.list.
    The store path now broadcasts the same history.checkpointed event
    the stateless action does."""
    history = History(checkpoint_on_store=True)
    server = await new_hocuspocus(extensions=[history], debounce=50)
    p = new_provider(server, name="store-mint")
    events: list = []
    _collect(p, events)
    try:
        await wait_synced(p)
        p.document.get_text("t").insert(0, "persist me")
        await retryable_assertion(
            lambda: _assert(
                any(e.get("event") == "history.checkpointed" for e in events)
            ),
            timeout=15,
        )
        minted = next(e for e in events if e["event"] == "history.checkpointed")
        assert minted["label"] == "store"
        assert history._docs["store-mint"].versions, "version list should hold it"
    finally:
        p.destroy()
        await server.destroy()
