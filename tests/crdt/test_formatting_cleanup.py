"""Formatting-cleanup passes (yjs cleanupYTextFormatting family).

Redundant ContentFormat markers — left behind when formatted text is
deleted, or when concurrent peers both format the same range — must be
garbage-collected without changing rendered content. Cleanup deletions
are ordinary CRDT deletes, so they must ALSO propagate: after a relay
round both peers hold the same (reduced) marker population and
identical deltas.
"""

import random

from hocuspocus_tpu.crdt import Doc
from hocuspocus_tpu.crdt.content import ContentFormat
from hocuspocus_tpu.crdt.types.ytext import cleanup_ytext_formatting
from hocuspocus_tpu.crdt.update import apply_update, encode_state_as_update


def _live_format_markers(ytext) -> int:
    count = 0
    item = ytext._start
    while item is not None:
        if not item.deleted and isinstance(item.content, ContentFormat):
            count += 1
        item = item.right
    return count


def _relay_until_converged(a, b, rounds: int = 5) -> None:
    for _ in range(rounds):
        apply_update(b, encode_state_as_update(a), "remote")
        apply_update(a, encode_state_as_update(b), "remote")


def test_deleting_formatted_text_cleans_its_markers():
    d = Doc()
    t = d.get_text("t")
    t.insert(0, "hello world")
    t.format(0, 5, {"bold": True})
    assert _live_format_markers(t) == 2  # open + close
    t.delete(0, 5)
    # the bolded text is gone; its markers straddle a pure-tombstone
    # gap and must be collected by the delete-time gap cleanup
    assert t.to_string() == " world"
    assert _live_format_markers(t) == 0
    assert t.to_delta() == [{"insert": " world"}]


def test_local_unformat_leaves_no_live_markers():
    """format/unformat on ONE doc cleans inline (yjs formatText deletes
    superseded markers as it walks); the full sweep then finds nothing."""
    d = Doc()
    t = d.get_text("t")
    t.insert(0, "abcdef")
    t.format(0, 3, {"bold": True})
    t.format(0, 3, {"bold": None})  # unbold
    assert t.to_delta() == [{"insert": "abcdef"}]
    assert _live_format_markers(t) == 0
    assert cleanup_ytext_formatting(t) == 0  # already clean


def test_full_sweep_removes_remote_duplicate_markers():
    """A third peer that merges two sides' CONCURRENT identical formats
    receives duplicate markers in one update; the remote hygiene pass
    (full sweep) reduces them to one live pair."""
    a = Doc()
    b = Doc()
    ta = a.get_text("t")
    ta.insert(0, "duplicated formatting")
    apply_update(b, encode_state_as_update(a), "remote")
    ta.format(0, 10, {"bold": True})
    b.get_text("t").format(0, 10, {"bold": True})

    c = Doc()
    tc = c.get_text("t")  # typed BEFORE applying: hygiene rides the observer
    apply_update(c, encode_state_as_update(a), "remote")
    apply_update(c, encode_state_as_update(b), "remote")
    assert tc.to_delta() == [
        {"insert": "duplicated", "attributes": {"bold": True}},
        {"insert": " formatting"},
    ]
    # the duplicate OPEN marker is shadowed and collected; the duplicate
    # close (null) markers survive — gap passes only examine runs whose
    # start is non-countable/deleted, faithful to yjs's sweep
    assert _live_format_markers(tc) == 3, _live_format_markers(tc)


def test_concurrent_same_format_dedups_after_exchange():
    """Both peers bold the same range concurrently; after the exchange
    each side holds duplicate markers — the remote-transaction hygiene
    pass must reduce them while keeping the rendered delta intact and
    CONVERGENT (cleanup deletes relay like any other delete)."""
    a = Doc()
    b = Doc()
    ta = a.get_text("t")
    tb = b.get_text("t")
    ta.insert(0, "shared text here")
    apply_update(b, encode_state_as_update(a), "remote")
    assert tb.to_string() == "shared text here"

    ta.format(0, 6, {"bold": True})
    tb.format(0, 6, {"bold": True})
    _relay_until_converged(a, b)

    want = [
        {"insert": "shared", "attributes": {"bold": True}},
        {"insert": " text here"},
    ]
    assert ta.to_delta() == want
    assert tb.to_delta() == want
    # the duplicate OPEN marker is collected on both sides (4 -> 3; the
    # shadowed close marker survives, faithful to yjs's sweep scope)
    assert _live_format_markers(ta) == 3, _live_format_markers(ta)
    assert _live_format_markers(tb) == 3
    # and the two stores agree byte-for-byte
    assert encode_state_as_update(a) == encode_state_as_update(b)


def test_gap_cleanup_uses_identity_for_object_attrs():
    """yjs cleanupFormattingGap compares attribute values with `===`:
    value equality for primitives, REFERENCE identity for objects. A
    marker restating an equal-but-DISTINCT object attribute (the normal
    shape after a wire decode — every decode builds fresh objects) is
    therefore KEPT by yjs peers; deleting it with deep equality
    diverges our tombstone layout from yjs interop expectations
    (round-5 review). Primitive values still dedup."""

    def build(attr_value):
        a = Doc()
        ta = a.get_text("t")
        ta.insert(0, "abcdefgh")
        ta.format(0, 8, {"c": attr_value})
        b = Doc()
        b.get_text("t")
        apply_update(b, encode_state_as_update(a), "remote")
        # carve an unformat out of the middle: ...[c=None]def[reopen c]...
        b.get_text("t").format(3, 3, {"c": None})
        c = Doc()
        tc = c.get_text("t")
        apply_update(c, encode_state_as_update(b), "remote")
        # tombstone "def": the gap now holds the None marker, the
        # tombstones, and the REOPEN marker restating the start attr
        tc.delete(3, 3)
        return tc

    tc = build({"x": 1})
    # the reopen marker's dict is EQUAL to the start attribute but a
    # DISTINCT decoded object: identity semantics keep it (open +
    # reopen + close = 3 live markers), rendered content unchanged
    assert tc.to_string() == "abcgh"
    assert _live_format_markers(tc) == 3, _live_format_markers(tc)
    assert all(
        op.get("attributes") == {"c": {"x": 1}} for op in tc.to_delta()
    ), tc.to_delta()

    tc = build(True)
    # primitives compare by value under ===: the restatement is
    # redundant and collected
    assert tc.to_string() == "abcgh"
    assert _live_format_markers(tc) == 2, _live_format_markers(tc)
    assert all(
        op.get("attributes") == {"c": True} for op in tc.to_delta()
    ), tc.to_delta()


def test_cleanup_converges_under_random_format_churn():
    """Random concurrent format/insert/delete churn with relays: marker
    populations stay bounded and the peers always converge."""
    for seed in range(8):
        rng = random.Random(4200 + seed)
        a = Doc()
        b = Doc()
        ta = a.get_text("t")
        tb = b.get_text("t")
        ta.insert(0, "x" * 60)
        apply_update(b, encode_state_as_update(a), "remote")
        for _round in range(12):
            for t in (ta, tb):
                vis = len(t.to_string())
                op = rng.random()
                if op < 0.4 and vis > 10:
                    start = rng.randrange(vis - 5)
                    t.format(start, 5, {"bold": rng.random() < 0.5 or None})
                elif op < 0.7:
                    t.insert(rng.randrange(vis + 1), "y")
                elif vis > 4:
                    t.delete(rng.randrange(vis - 2), 2)
            _relay_until_converged(a, b, rounds=2)
        _relay_until_converged(a, b)
        assert ta.to_string() == tb.to_string(), seed
        assert ta.to_delta() == tb.to_delta(), seed
        assert encode_state_as_update(a) == encode_state_as_update(b), seed
