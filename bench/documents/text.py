"""The kind `text`: every document is one shared text, `seeded.TEXT_TYPE`,
as a `Y.Text` holds it. Its first states are `lib/seeded.py`'s (one string
item of `doc_units` letters from one client, no delete set), its reference
is `lib/reference.py`'s `ReferenceText`, and the device's view is the
plane's own read-back of its arena (`MergePlane.text`). `lib/kinds.py` says
what a kind gives the harness.
"""

from __future__ import annotations

import seeded  # bench/lib, on the path of every process that loads a kind
from compare import by_doc
from reference import ReferenceText as Reference
from reference import decode_update

ROOT = seeded.TEXT_TYPE
VIEWS = "texts"
CHECKS = ("updates_not_as_meant", "texts_not_as_typed")


def first_states(seed: int, docs: int, config: dict) -> "list[tuple[int, str]]":
    """(client, text) of each document: `doc_units` letters (`seeded.first_texts`)."""
    texts = seeded.first_texts(seed, docs, int(config["doc_units"]))
    return [(seeded.first_client(seed, doc), text) for doc, text in enumerate(texts)]


def first_writes(state: "tuple[int, str]") -> "list[tuple[int, bytes]]":
    client, text = state
    return [(client, seeded.text_update(client, text))]


def first_view(state: "tuple[int, str]") -> str:
    return state[1]


def first_in_row(config: dict, arena: str) -> int:
    """A first text takes `doc_units` units of a unit row, and one entry of
    a run-length row: `seeded.text_update` writes it as one string item."""
    return int(config["doc_units"]) if arena == "unit" else 1


def edited(document):
    return document.get_text(ROOT)


def view(document) -> str:
    return document.get_text(ROOT).to_string()


async def device_view(served, name: str) -> "str | None":
    return await served.on_plane(name, lambda plane: plane.text(name))


def reference_view(reference: Reference) -> str:
    return reference.text()


def checks(first: list, log: "list[tuple]", references: "list[Reference]", only_appends: bool) -> dict:
    return {
        "updates_not_as_meant": not_as_meant(log),
        "texts_not_as_typed": not_as_typed(first, log, references) if only_appends else 0,
    }


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


def not_as_typed(first: "list[tuple[int, str]]", log: "list[tuple]", references: "list[Reference]") -> int:
    """Documents with one writer that only appends have one possible text,
    known from the seed alone: the first text and then every run in order."""
    wrong = 0
    for (_client, text), entries, want in zip(first, by_doc(log, len(first)), references):
        if len({entry[2] for entry in entries}) <= 1 and not any(entry[4] for entry in entries):
            wrong += want.text() != text + "".join(entry[3] for entry in entries)
    return wrong
