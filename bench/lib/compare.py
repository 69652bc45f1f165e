"""What decides `correct`: every driven document, as each client, the
server, the device and the write-ahead log hold it once the window has
closed and the queues have drained, against the plain reference of its kind
(`lib/kinds.py`) merged from the document's first state (made from the seed)
and the updates the clients put on the wire; the kind's own checks (for
`text`: each update against what its client meant to type, drawn from the
seed); and a seeded sample of the resident documents against their first
states.

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

import kinds
from reference import signed32_before, unsigned_before

CONTROLS = ("drop-last-update", "signed-client-order", "wal-drop-last-record")


def default_kind():
    """The kind of a configuration that names none (`lib/kinds.py`)."""
    return kinds.load(kinds.DEFAULT)


def differing(kind, place: str) -> str:
    """The number of views read at `place` that differ from the reference's."""
    return f"{place}_{kind.VIEWS}_differing"


def limits(kind=None) -> dict:
    """{number compared: its limit}, in the order they are printed."""
    kind = kind or default_kind()
    client, server, device, wal, resident = (
        differing(kind, place) for place in ("client", "server", "device", "wal", "resident")
    )
    names = ["updates_undelivered", *kind.CHECKS, client, server, device, "state_vectors_differing", wal, resident]
    return dict.fromkeys(names, 0)


def by_doc(log: "list[tuple]", docs: int) -> "list[list[tuple]]":
    """The clients' log, [(document, update, client id, run, units cut)], per document."""
    per_doc: "list[list[tuple]]" = [[] for _ in range(docs)]
    for entry in log:
        per_doc[entry[0]].append(entry)
    return per_doc


def merged(first: list, log: "list[tuple]", control: "str | None" = None, *, kind=None) -> list:
    """The reference of each document of `kind`: its first state, as the
    kind's `first_states` gave it, then the updates in the order the clients
    made them."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"no control {control!r} (has {CONTROLS})")
    kind = kind or default_kind()
    before = signed32_before if control == "signed-client-order" else unsigned_before
    references = []
    for state, entries in zip(first, by_doc(log, len(first))):
        updates = [update for _client, update in kind.first_writes(state)] + [entry[1] for entry in entries]
        if control == "drop-last-update":
            updates = updates[:-1]
        reference = kind.Reference(before)
        reference.apply_updates(updates)
        references.append(reference)
    return references


def replayed(kind, updates: "list[bytes]") -> "tuple[object, dict] | None":
    try:
        reference = kind.Reference(unsigned_before)
        reference.apply_updates(updates)
    except (ValueError, IndexError):
        return None
    return kind.reference_view(reference), reference.state_vector()


def compare(references: list, observed: dict, first=None, log=None, only_appends: bool = False, *, kind=None) -> dict:
    """{number compared: [value, limit]}. `observed` has "undelivered" (a
    count), "docs" (per driven document, in order: "clients", a list of
    (view, state vector) per client; "server" and "device", a view or None;
    "wal", the log's payloads) and "resident" (a list of (view wanted,
    server's, device's)). `first` and `log` as `merged` takes them."""
    kind = kind or default_kind()
    limit = limits(kind)
    numbers = dict.fromkeys(limit, 0)
    numbers["updates_undelivered"] = observed["undelivered"]
    if log is not None:
        numbers.update(kind.checks(first, log, references, only_appends))
    for want, got in zip(references, observed["docs"]):
        view, vector = kind.reference_view(want), want.state_vector()
        for got_view, got_vector in got["clients"]:
            numbers[differing(kind, "client")] += got_view != view
            numbers["state_vectors_differing"] += got_vector != vector
        numbers[differing(kind, "server")] += got["server"] != view
        numbers[differing(kind, "device")] += got["device"] != view
        numbers[differing(kind, "wal")] += replayed(kind, got["wal"]) != (view, vector)
    for wanted, server, device in observed["resident"]:
        numbers[differing(kind, "resident")] += (server != wanted) + (device != wanted)
    return {name: [int(value), limit[name]] for name, value in numbers.items()}


def correct(compared: dict) -> bool:
    return all(value <= limit for value, limit in compared.values())


def as_observed(references: list, logs: "list[list[bytes]]", control: "str | None" = None, *, kind=None) -> dict:
    """A set of references put in the program's place, beside the log as
    the program left it (`logs`, per document)."""
    kind = kind or default_kind()
    if control == "wal-drop-last-record":
        # the journal may hold a record a second time: it is lost there too
        logs = [[p for p in payloads if p != payloads[-1]] for payloads in logs]
    views = [kind.reference_view(r) for r in references]
    return {
        "undelivered": 0,
        "docs": [
            {"clients": [(view, r.state_vector())], "server": view, "device": view, "wal": payloads}
            for view, r, payloads in zip(views, references, logs)
        ],
        "resident": [],
    }
