"""The generator of cursor editing: `writers.Generator` with another `send`.
Each writer keeps a cursor in its document and every update is one operation
on one unit at it, as the editing trace of crdt-benchmarks B4 has them: a
delete of the unit before the cursor, or an insert of one letter at it. The
cursor stays where the last operation left it for a run of operations, then
jumps. Connections, the open loop, rates per document, the records and the
result are `writers`' own (`generators/writers.py`: imported, not copied).

A traffic mix that names this generator sets, besides what `writers` reads
(`loop`, `client_processes`, `warmup_seconds`, the rate and its spread):

  delete_share             share of operations that delete the unit before
                           the cursor; at position 0 it is an insert instead
  cursor_run_mean_ops      after each operation the run ends with probability
                           1 / this (geometric run lengths), and the cursor jumps
  cursor_jump              shares of "local" (uniform within
                           cursor_local_span_units of where it was, clipped to
                           the text) and "uniform" (anywhere in the text)
  cursor_local_span_units  how far a local jump reaches, either way

A document's first cursor is uniform over its text. Every draw comes from the
document's own generator (`writers.doc_rng`), so the same seed gives the same
operations. Log entries keep `writers`' form (document, update, client id,
run, units cut) with a run of one letter or none and 1 or 0 units cut.

`writers` clocks an update as applied by a peer once the peer's state vector
has reached the writer's clock after it. A delete alone moves no clock, so
here a peer has applied an update once it also holds as many tombstones as
the writer's document held after it (units ever inserted, by the state
vector, less the text's length). That count names the update only where one
author deletes in a document: `writers_per_doc` has to be 1.
"""

from __future__ import annotations

import asyncio

from clients import load_generator  # bench/lib, on the path of every process that loads a generator

writers = load_generator("writers")


def most_units_added(mix: dict, all_docs: int, seconds: float) -> int:
    """The most units one document can grow by in a run: one unit an update,
    had every operation of the hottest document been an insert."""
    return writers.most_updates(mix, all_docs, seconds)


def most_entries_added(mix: dict, all_docs: int, seconds: float) -> int:
    """The most entries a run-length row (`--tpu-arena rle`) of one document
    can grow by in a run: two an update. Every update is one operation of the
    device on one unit, and an operation appends at most two entries
    (`tpu/kernels_rle.py`): an insert inside a run its own entry and the
    run's tail, a delete of a unit inside a run the unit and the tail behind
    it. A letter typed where the last one ended costs one, which the bound
    does not count on."""
    return 2 * most_units_added(mix, all_docs, seconds)


class Cursor:
    """Where one writer edits, and the draw of its next operation."""

    __slots__ = ("at", "_delete_share", "_end_of_run", "_jumps", "_jump_weights", "_span")

    def __init__(self, mix: dict, rng, length: int) -> None:
        self.at = rng.randrange(length + 1)
        self._delete_share = float(mix["delete_share"])
        self._end_of_run = 1.0 / float(mix["cursor_run_mean_ops"])
        self._jumps = sorted(mix["cursor_jump"])
        self._jump_weights = [mix["cursor_jump"][kind] for kind in self._jumps]
        self._span = int(mix["cursor_local_span_units"])

    def draw(self, rng, length: int) -> "tuple[int, str]":
        """(position, letter) of the next operation on a text of `length`
        units; no letter means: delete the unit at `position`. The cursor
        moves with the operation, and jumps where its run ends."""
        at = min(self.at, length)
        if at > 0 and rng.random() < self._delete_share:
            at -= 1
            operation = (at, "")
            length -= 1
        else:
            operation = (at, writers.ALPHABET[rng.randrange(len(writers.ALPHABET))])
            at += 1
            length += 1
        if rng.random() < self._end_of_run:
            if rng.choices(self._jumps, self._jump_weights)[0] == "local":
                at = min(max(at + rng.randint(-self._span, self._span), 0), length)
            else:
                at = rng.randrange(length + 1)
        self.at = at
        return operation


def tombstones(document, body) -> int:
    """Units of `document` that were inserted and are deleted now."""
    return sum(document.store.get_state_vector().values()) - len(body)


class Edit(writers.Update):
    """An `Update` with the tombstones its writer's document held after it."""

    __slots__ = ("tombstones",)


class Generator(writers.Generator):
    def __init__(self, spec: dict) -> None:
        super().__init__(spec)
        if self.writers != 1:
            raise ValueError("editors: a delete's arrival is told by a count of tombstones, so one writer a document")
        self.cursors: "dict[int, Cursor]" = {}  # by document

    def _on_update(self, doc: int, client: int, provider):
        """`writers`' observer of a peer's document, which takes an update
        for applied once the peer has reached the writer's clock and its
        tombstones (see the module's text)."""
        records, writer_id, body, pointer = None, None, None, 0

        def on_update(update: bytes, origin, document, *_rest) -> None:
            nonlocal records, writer_id, body, pointer
            if origin is not provider:
                self._made = update  # a local edit: `send` logs it with what it meant
                return
            if client == 0:
                return  # the writer's own document: nobody else writes
            if records is None:
                records, writer_id = self.records[doc][0], self.client_ids[doc][0]
                body = self.document_kind.edited(document)
            if pointer == len(records):
                return
            at = writers.now()
            reached, buried = document.store.get_state(writer_id), tombstones(document, body)
            while pointer < len(records):
                record = records[pointer]
                if record.end_clock > reached or record.tombstones > buried:
                    break
                pointer += 1
                record.remaining -= 1
                if record.remaining == 0:
                    record.done = at
                    self.outstanding -= 1
                    if self.closed and self.sending and at < self.window_end:
                        asyncio.get_running_loop().call_soon(self.send, doc, 0, at)

        return on_update

    def send(self, doc: int, writer: int, due: float) -> None:
        """One operation of the mix from one writer, at its cursor."""
        rng = self.rngs[doc]
        document = self.providers[doc][writer].document
        body = self.document_kind.edited(document)
        length = len(body)
        cursor = self.cursors.get(doc)
        if cursor is None:
            cursor = self.cursors[doc] = Cursor(self.mix, rng, length)
        at, letter = cursor.draw(rng, length)
        started = writers.now()
        self._made = None
        if letter:
            body.insert(at, letter)
        else:
            body.delete(at, 1)
        self.log.append((doc, self._made, document.client_id, letter, 0 if letter else 1))
        measured = self.window_start <= (started if self.closed else due) < self.window_end
        clock = document.store.get_state(document.client_id)
        edit = Edit(measured, due, started, clock, self.clients - 1)
        edit.tombstones = tombstones(document, body)
        self.records[doc][writer].append(edit)
        self.outstanding += self.clients > 1
        if measured and not self.closed:
            self.late.append(started - due)
