"""What decides `correct`: every driven document, as each client, the
server, the device arena and the write-ahead log hold it once the window
has closed and the queues have drained, against the plain reference merged
from the document's first text (made from the seed) and the updates the
clients put on the wire; each of those updates against what its client
meant to type (drawn from the seed); and a seeded sample of the resident
documents against their first texts.

Each number compared is a count with the limit 0 (an exact comparison).
Health facts are not here: `serve.Served.health`.

The controls put the reference in the program's place with one guarantee
of the configuration broken, and have to come out as not correct:

  drop-last-update      the last update each document received is
                        acknowledged and lost (a later or rarer flush)
  signed-client-order   concurrent inserts at one position ordered by the
                        client id as int32, not as the unsigned number Yjs
                        states (what an int32 device compare would do)
  wal-drop-last-record  the log loses each document's last record (a
                        commit deferred past the broadcast it should gate)
"""

from __future__ import annotations

from reference import ReferenceText, decode_update, signed32_before, unsigned_before
from seeded import text_update

LIMITS = {
    "updates_undelivered": 0,
    "updates_not_as_meant": 0,
    "texts_not_as_typed": 0,
    "client_texts_differing": 0,
    "server_texts_differing": 0,
    "device_texts_differing": 0,
    "state_vectors_differing": 0,
    "wal_texts_differing": 0,
    "resident_texts_differing": 0,
}
CONTROLS = ("drop-last-update", "signed-client-order", "wal-drop-last-record")


def by_doc(log: "list[tuple]", docs: int) -> "list[list[tuple]]":
    """The clients' log, [(document, update, client id, run, units cut)], per document."""
    per_doc: "list[list[tuple]]" = [[] for _ in range(docs)]
    for entry in log:
        per_doc[entry[0]].append(entry)
    return per_doc


def merged(first: "list[tuple[int, str]]", log: "list[tuple]", control: "str | None" = None) -> "list[ReferenceText]":
    """The reference text of each document: its first text, typed by
    `first[doc][0]`, then the updates in the order the clients made them."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"no control {control!r} (has {CONTROLS})")
    before = signed32_before if control == "signed-client-order" else unsigned_before
    texts = []
    for (client, text), entries in zip(first, by_doc(log, len(first))):
        updates = [text_update(client, text)] + [entry[1] for entry in entries]
        if control == "drop-last-update":
            updates = updates[:-1]
        reference = ReferenceText(before)
        reference.apply_updates(updates)
        texts.append(reference)
    return texts


def not_as_meant(log: "list[tuple]") -> int:
    """Updates on the wire that do not say what their client meant: one run
    of text from that client, after that many units deleted."""
    wrong = 0
    for _doc, update, client, run, cut in log:
        try:
            inserts, deletes = decode_update(update)
        except (ValueError, IndexError, TypeError):
            wrong += 1
            continue
        said = "".join(text for author, _clock, _left, _right, text in inserts if author == client)
        wrong += (
            said != run
            or any(author != client for author, *_rest in inserts)
            or sum(length for _client, _clock, length in deletes) != cut
        )
    return wrong


def not_as_typed(first: "list[tuple[int, str]]", log: "list[tuple]", reference: "list[ReferenceText]") -> int:
    """Documents with one writer that only appends have one possible text,
    known from the seed alone: the first text and then every run in order."""
    wrong = 0
    for (_client, text), entries, want in zip(first, by_doc(log, len(first)), reference):
        if len({entry[2] for entry in entries}) <= 1 and not any(entry[4] for entry in entries):
            wrong += want.text() != text + "".join(entry[3] for entry in entries)
    return wrong


def replayed(updates: "list[bytes]") -> "tuple[str, dict] | None":
    try:
        reference = ReferenceText()
        reference.apply_updates(updates)
    except (ValueError, IndexError):
        return None
    return reference.text(), reference.state_vector()


def compare(reference: "list[ReferenceText]", observed: dict, first=None, log=None, only_appends: bool = False) -> dict:
    """{number compared: [value, limit]}. `observed` has "undelivered" (a
    count), "docs" (per driven document, in order: "clients", a list of
    (text, state vector) per client; "server" and "device", a text or None;
    "wal", the log's payloads) and "resident" (a list of (text wanted,
    server's, device's)). `first` and `log` as `merged` takes them."""
    numbers = dict.fromkeys(LIMITS, 0)
    numbers["updates_undelivered"] = observed["undelivered"]
    if log is not None:
        numbers["updates_not_as_meant"] = not_as_meant(log)
        if only_appends:
            numbers["texts_not_as_typed"] = not_as_typed(first, log, reference)
    for want, got in zip(reference, observed["docs"]):
        text, vector = want.text(), want.state_vector()
        for got_text, got_vector in got["clients"]:
            numbers["client_texts_differing"] += got_text != text
            numbers["state_vectors_differing"] += got_vector != vector
        numbers["server_texts_differing"] += got["server"] != text
        numbers["device_texts_differing"] += got["device"] != text
        numbers["wal_texts_differing"] += replayed(got["wal"]) != (text, vector)
    for wanted, server, device in observed["resident"]:
        numbers["resident_texts_differing"] += (server != wanted) + (device != wanted)
    return {name: [int(value), LIMITS[name]] for name, value in numbers.items()}


def correct(compared: dict) -> bool:
    return all(value <= limit for value, limit in compared.values())


def as_observed(reference: "list[ReferenceText]", logs: "list[list[bytes]]", control: "str | None" = None) -> dict:
    """A set of reference texts put in the program's place, beside the log
    as the program left it (`logs`, per document)."""
    if control == "wal-drop-last-record":
        # the journal may hold a record a second time: it is lost there too
        logs = [[p for p in payloads if p != payloads[-1]] for payloads in logs]
    return {
        "undelivered": 0,
        "docs": [
            {
                "clients": [(r.text(), r.state_vector())],
                "server": r.text(),
                "device": r.text(),
                "wal": payloads,
            }
            for r, payloads in zip(reference, logs)
        ],
        "resident": [],
    }
