"""The general generator of editing traffic: real `HocuspocusProvider`
clients, each on a loopback websocket of its own, that insert runs of text
into their documents and measure when every peer has applied them. It runs
in a client process (`lib/clients.py`), never in the server's.

A traffic mix (`bench/traffic/<name>.json`) that names this generator sets:

  loop              "open": updates are due on a schedule drawn from the
                    seed, whatever the system does, and every one of them
                    is sent, however late; "closed": a document has
                    `concurrent_writers_per_doc` updates in flight, and when
                    one has been applied by every peer of the document, one
                    of its writers, drawn anew, sends the next
  client_processes  how many processes share the driven documents
  warmup_seconds    traffic that runs before the window opens, unmeasured
  run_units         [least, most] units inserted by one update
  position_mix      where an update inserts: shares of "end", "hot" (the
                    middle of the text, where everyone else is too) and
                    "uniform"
  replace_share     share of updates that first delete a range at their
                    position (typing over a selection), delete_units long
  rate_updates_per_s        open loop: updates per second over all documents
  doc_rate_pareto_alpha     open loop: per-document rates are the quantiles
                    of this Pareto law, dealt to the documents by the seed,
                    each plane alike
  doc_rate_cap_over_mean    ... cut off at this multiple of the mean rate

The configuration adds `clients_per_doc`, `writers_per_doc` and `doc_units`
(the text a document starts with: the server recovers it from its log, the
clients sync it and check its length). The spec names the kind of document
(`lib/kinds.py`): a client edits the shared type the kind gives it and
reports what it holds as the kind reads it.

Every document draws from a generator of its own, seeded by the run's seed
and the document's number, so how documents are dealt to client processes
changes nothing. Every seed gets the same set of document rates, in another
order, and in an open loop the same due times, dealt to the documents in
another order: what a run's seed changes is which document does what, not
how much work arrives when.
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import time

import kinds  # bench/lib, on the path of every process that loads a generator

ALPHABET = "etaoinshrdlucmfwypvbgkqjxz"
DUE_TIMES_SEED = 0x5EED  # an open loop's due times: the same for every run's seed
now = time.monotonic  # CLOCK_MONOTONIC: one clock for every process of the host


class Update:
    """One update a client made: when it was due and sent, the clock its
    client reached with it, and when the last intended peer applied it."""

    __slots__ = ("measured", "due", "sent", "end_clock", "remaining", "done")

    def __init__(self, measured: bool, due: float, sent: float, end_clock: int, remaining: int) -> None:
        self.measured = measured
        self.due = due
        self.sent = sent
        self.end_clock = end_clock
        self.remaining = remaining
        self.done = None if remaining else sent


def doc_rates(mix: dict, docs: int, seed: int) -> "list[float]":
    """Updates per second of each document: fixed quantiles of a Pareto
    law, capped, scaled to the mix's total, dealt out by the seed. Where the
    documents come grouped by plane (`docs_per_plane` of them in a row, as
    `run.py` lists them), the rates are dealt a round at a time, as many as
    there are planes, the largest left to the plane that holds least so far:
    every seed gives the planes the same loads, only to other planes."""
    alpha = mix["doc_rate_pareto_alpha"]
    weights = [(1.0 - (i + 0.5) / docs) ** (-1.0 / alpha) for i in range(docs)]
    for _ in range(8):  # the cap moves the mean, so settle it
        cap = mix["doc_rate_cap_over_mean"] * sum(weights) / docs
        weights = [min(w, cap) for w in weights]
    scale = mix["rate_updates_per_s"] / sum(weights)
    quantiles = [w * scale for w in weights]
    per_plane = int(mix.get("docs_per_plane") or docs)
    if docs % per_plane:
        per_plane = docs
    planes = docs // per_plane
    rng = random.Random(seed)
    tie_break = rng.sample(range(planes), planes)
    loads = [0.0] * planes
    dealt: "list[list[float]]" = [[] for _ in range(planes)]
    ranked = sorted(quantiles, reverse=True)
    for at in range(0, docs, planes):  # the largest rates left go to the lightest planes
        lightest = sorted(range(planes), key=lambda plane: (loads[plane], tie_break[plane]))
        for plane, rate in zip(lightest, ranked[at : at + planes]):
            dealt[plane].append(rate)
            loads[plane] += rate
    rates = []
    for plane_rates in dealt:
        rng.shuffle(plane_rates)
        rates.extend(plane_rates)
    return rates


def doc_rng(seed: int, doc: int) -> random.Random:
    return random.Random(seed * 1_000_003 + doc)


def open_schedule(mix: dict, all_docs: int, mine: "list[int]", seconds: float, seed: int) -> "list[tuple]":
    """(due, document) of every update of the documents `mine`, from
    `-warmup_seconds` to `seconds`, sorted by time. The due times are drawn
    once, from no seed, so that every seed offers the same arrivals; the seed
    deals them out to the documents, to each as many as its rate asks."""
    start = -float(mix["warmup_seconds"])
    span = seconds - start
    owners = [doc for doc, rate in enumerate(doc_rates(mix, all_docs, seed)) for _ in range(round(rate * span))]
    times = random.Random(DUE_TIMES_SEED)
    dues = sorted(start + times.random() * span for _ in owners)
    random.Random(seed * 1_000_003 + 500_009).shuffle(owners)
    mine = set(mine)
    return [(due, doc) for due, doc in zip(dues, owners) if doc in mine]


def most_updates(mix: dict, all_docs: int, seconds: float) -> int:
    """The most updates one document of an open loop is sent in a run,
    warm-up included: every seed deals out the same set of rates."""
    return round(max(doc_rates(mix, all_docs, 0)) * (seconds + float(mix["warmup_seconds"])))


def most_units_added(mix: dict, all_docs: int, seconds: float) -> int:
    """The most units one document of an open loop can grow by in a run."""
    return most_updates(mix, all_docs, seconds) * int(mix["run_units"][1])


def most_entries_added(mix: dict, all_docs: int, seconds: float) -> int:
    """The most entries a run-length row (`--tpu-arena rle`) of one document
    of an open loop can grow by in a run. An operation of the device appends
    at most two entries (`tpu/kernels_rle.py`): an insert its own run and the
    tail of the run it splits, a delete the tails behind its two ends. An
    update is one insert, and where the mix types over a selection one delete
    before it for every range of the delete set: the units cut can each be
    another author's, so `delete_units[1]` ranges at the most."""
    operations = 1 + (int(mix["delete_units"][1]) if mix["replace_share"] else 0)
    return most_updates(mix, all_docs, seconds) * 2 * operations


class Generator:
    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.mix = mix = spec["mix"]
        self.url = spec["url"]
        self.document_kind = kinds.load(spec["document"])
        self.seed = int(spec["seed"])
        self.seconds = float(spec["seconds"])
        self.docs = [(int(d["index"]), d["name"]) for d in spec["docs"]]
        self.all_docs = int(spec["all_docs"])
        self.clients = int(spec["clients_per_doc"])
        self.writers = int(spec["writers_per_doc"])
        self.rngs = {doc: doc_rng(self.seed, doc) for doc, _name in self.docs}
        self.providers: "dict[int, list]" = {}
        self.client_ids: "dict[int, list[int]]" = {}
        self.records: "dict[int, list[list[Update]]]" = {}  # [document][writer] in sending order
        self.pointers: "dict[int, list[list[int]]]" = {}  # [document][peer][writer]
        self.log: "list[tuple]" = []  # (document, update, client id, run, units cut) as the clients made them
        self.late: "list[float]" = []  # open loop: sent - due, window only
        self.unsent: "list[float]" = []  # open loop: due times of updates never sent
        self.outstanding = 0  # updates sent that some intended peer has not applied yet
        self.window_start = float("inf")
        self.window_end = float("inf")
        self.sending = False
        self.closed = mix["loop"] == "closed"
        self.cpu_s = 0.0
        self.gave_up = None
        self._kinds = sorted(mix["position_mix"])
        self._kind_weights = [mix["position_mix"][k] for k in self._kinds]

    # -- clients -----------------------------------------------------------

    async def connect(self, group: int = 16) -> None:
        """One provider per document and client, `group` documents at a
        time; each syncs the document's first text from the server."""
        from hocuspocus_tpu.aio import await_synced
        from hocuspocus_tpu.crdt import Doc
        from hocuspocus_tpu.provider import HocuspocusProvider

        for at in range(0, len(self.docs), group):
            fresh = []
            for doc, name in self.docs[at : at + group]:
                ids = random.Random(self.seed * 1_000_003 + doc + 250_007)
                clients: "list[int]" = []
                while len(clients) < self.clients:
                    # bit 30 keeps them clear of the first text's author; bit
                    # 31 puts them on both sides of 2**31: their order is unsigned
                    cid = ids.getrandbits(30) | 1 << 30 | (len(clients) % 2) << 31
                    if cid not in clients:
                        clients.append(cid)
                row = []
                for client, cid in enumerate(clients):
                    document = Doc()
                    document.client_id = cid
                    provider = HocuspocusProvider(name=name, url=self.url, document=document)
                    document.on("update", self._on_update(doc, client, provider))
                    row.append(provider)
                self.providers[doc] = row
                self.client_ids[doc] = clients
                self.records[doc] = [[] for _ in range(self.writers)]
                self.pointers[doc] = [[0] * self.writers for _ in clients]
                fresh.extend(row)
            await await_synced(fresh, timeout=180, what="bench clients")
        short = [
            name
            for doc, name in self.docs
            for provider in self.providers[doc]
            if len(self.document_kind.edited(provider.document)) != int(self.mix["doc_units"])
        ]
        if short:
            raise RuntimeError(f"clients synced without the document's first text: {short[:5]}")

    def _on_update(self, doc: int, client: int, provider):
        records, pointers, ids = None, None, None

        def on_update(update: bytes, origin, document, *_rest) -> None:
            nonlocal records, pointers, ids
            if origin is not provider:
                self._made = update  # a local edit: `_edit` logs it with what it meant
                return
            if records is None:
                records, pointers, ids = self.records[doc], self.pointers[doc][client], self.client_ids[doc]
            at = now()
            state = document.store.get_state
            for writer in range(self.writers):
                if writer == client:
                    continue
                sent = records[writer]
                pointer = pointers[writer]
                if pointer == len(sent):
                    continue
                reached = state(ids[writer])
                while pointer < len(sent) and sent[pointer].end_clock <= reached:
                    record = sent[pointer]
                    pointer += 1
                    record.remaining -= 1
                    if record.remaining == 0:
                        record.done = at
                        self.outstanding -= 1
                        if self.closed and self.sending and at < self.window_end:
                            again = self.rngs[doc].randrange(self.writers)
                            asyncio.get_running_loop().call_soon(self.send, doc, again, at)
                pointers[writer] = pointer

        return on_update

    # -- edits -------------------------------------------------------------

    def send(self, doc: int, writer: int, due: float) -> None:
        """One update of the mix from one writer: a run inserted at a drawn
        position, after a deleted range where the mix says so."""
        rng, mix = self.rngs[doc], self.mix
        document = self.providers[doc][writer].document
        body = self.document_kind.edited(document)
        length = len(body)
        kind = rng.choices(self._kinds, self._kind_weights)[0]
        at = length if kind == "end" else length // 2 if kind == "hot" else rng.randrange(length + 1)
        units = rng.randint(*mix["run_units"])
        cut = 0
        if rng.random() < mix["replace_share"]:
            cut = min(rng.randint(*mix["delete_units"]), length - at)
        offset = rng.randrange(len(ALPHABET))
        run = (ALPHABET * (units // len(ALPHABET) + 2))[offset : offset + units]
        started = now()
        self._made = None
        if cut:
            document.transact(lambda _txn: (body.delete(at, cut), body.insert(at, run)))
        else:
            body.insert(at, run)
        self.log.append((doc, self._made, document.client_id, run, cut))
        measured = self.window_start <= (started if self.closed else due) < self.window_end
        clock = document.store.get_state(document.client_id)
        self.records[doc][writer].append(Update(measured, due, started, clock, self.clients - 1))
        self.outstanding += self.clients > 1
        if measured and not self.closed:
            self.late.append(started - due)

    async def delivered(self, deadline: float) -> bool:
        """Wait until every update sent so far is applied by all its peers."""
        while self.outstanding:
            if now() > deadline:
                return False
            await asyncio.sleep(0.005)
        return True

    # -- the window --------------------------------------------------------

    async def run(self, opens_at: float) -> None:
        """Warm-up traffic, then the measured window, in one unbroken
        stream; then the wait for what is still in flight. The window is
        [opens_at, opens_at + seconds) on the host's monotonic clock."""
        warmup = float(self.mix["warmup_seconds"])
        self.window_start = opens_at
        self.window_end = opens_at + self.seconds
        give_up = self.window_end + float(self.spec["grace_seconds"])  # an update may still arrive that long
        mine = [doc for doc, _name in self.docs]
        self.sending = True
        loop = asyncio.get_running_loop()
        loop.call_later(max(opens_at - now(), 0), self._mark_cpu, -1.0)
        loop.call_later(max(self.window_end - now(), 0), self._mark_cpu, 1.0)
        if self.closed:  # the first updates, spread over the first half of the warm-up
            start = opens_at - warmup
            for nth, doc in enumerate(mine):
                due = start + warmup / 2 * nth / len(mine)
                first = self.rngs[doc].sample(range(self.writers), int(self.mix["concurrent_writers_per_doc"]))
                for writer in first:
                    loop.call_later(max(due - now(), 0), self.send, doc, writer, due)
            await asyncio.sleep(max(self.window_end - now(), 0))
        else:
            events = open_schedule(self.mix, self.all_docs, mine, self.seconds, self.seed)
            burst = 0
            for nth, (due, doc) in enumerate(events):
                wait = opens_at + due - now()
                if wait > 0.0005:
                    await asyncio.sleep(wait)
                    burst = 0
                elif burst >= 32:  # running late: still give the loop back
                    await asyncio.sleep(0)
                    burst = 0
                    if now() > give_up:  # a minute behind: what is left was never sent
                        self.unsent = [opens_at + d for d, _doc in events[nth:] if d >= 0]
                        break
                burst += 1
                self.send(doc, self.rngs[doc].randrange(self.writers), opens_at + due)
            await asyncio.sleep(max(self.window_end - now(), 0))
        self.sending = False
        await self.delivered(give_up)
        self.gave_up = now()

    def _mark_cpu(self, sign: float) -> None:
        self.cpu_s += sign * time.process_time()

    def result(self) -> dict:
        """What the server's process needs of this one, in plain types."""
        start = "sent" if self.closed else "due"
        measured = [
            (doc, getattr(r, start), r.done)
            for doc, writers in self.records.items()
            for records in writers
            for r in records
            if r.measured
        ]
        return {
            "records": measured,  # (document, start of the clock, applied by the last peer or None)
            "unsent": self.unsent,
            "gave_up": self.gave_up,
            "undelivered": self.outstanding,
            "log": self.log,
            "clients": {
                doc: [
                    (self.document_kind.view(p.document), dict(p.document.store.get_state_vector()))
                    for p in row
                ]
                for doc, row in self.providers.items()
            },
            "late_s": self.late,
            "cpu_s": self.cpu_s,
            "open_loop": not self.closed,
        }

    def close(self) -> None:
        for row in self.providers.values():
            for provider in row:
                with contextlib.suppress(Exception):
                    provider.destroy()
