"""`flush_busy_rows` / `flush_bucket_rows`: per device batch, the rows that
really carry ops and the bucket width they were padded to — the count the
benchmark's `bucket_fill_share` reads, for each layout a batch can take."""

import pytest

from hocuspocus_tpu.crdt import Doc
from hocuspocus_tpu.tpu.merge_plane import MergePlane

POPULATION = 16


def _plane_with_documents(run_merge: bool):
    plane = MergePlane(num_docs=POPULATION, capacity=256)
    plane.run_merge_enabled = run_merge
    docs, pending = [], []
    for i in range(POPULATION):
        plane.register(f"doc-{i}")
        doc, queue = Doc(), []
        doc.on("update", lambda update, *rest, queue=queue: queue.append(update))
        docs.append(doc)
        pending.append(queue)

    def type_into(indices) -> None:
        for i in indices:
            text = docs[i].get_text("t")
            text.insert(len(text), "typed ")
            for update in pending[i]:
                plane.enqueue_update(f"doc-{i}", update)
            pending[i].clear()

    return plane, type_into


@pytest.mark.parametrize(
    "layout, run_merge, busy",
    [
        ("fast", True, 3),  # run-append batch: nf rows in a bucket of bf
        ("sparse", False, 3),  # sparse integrate: the slow set's rows in a bucket of b
        ("dense", False, POPULATION),  # dense integrate: every busy slot, over all rows
    ],
)
def test_busy_rows_are_the_documents_flushed_and_fit_their_bucket(layout, run_merge, busy):
    plane, type_into = _plane_with_documents(run_merge)
    type_into(range(busy))
    before = dict(plane.counters)
    plane.flush()
    delta = {key: value - before[key] for key, value in plane.counters.items()}
    assert delta["flush_batches_" + layout] == 1
    assert sum(delta["flush_batches_" + kind] for kind in ("fast", "sparse", "dense")) == 1
    assert delta["flush_busy_rows"] == busy
    assert delta["flush_busy_rows"] <= delta["flush_bucket_rows"] <= POPULATION
    if layout == "dense":
        assert delta["flush_bucket_rows"] == POPULATION
    else:
        assert delta["flush_bucket_rows"] == plane.flush_stats["batch_b"] < POPULATION
    # a second cycle adds its own batch: the counters accumulate
    type_into(range(busy))
    plane.flush()
    assert plane.counters["flush_busy_rows"] - before["flush_busy_rows"] == 2 * busy
