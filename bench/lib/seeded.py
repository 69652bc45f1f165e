"""What a run starts from, made from the seed alone and by the benchmark's
own code: each document's first text and the Yjs update that holds it (the
kind `text`, `bench/documents/text.py`), and the write-ahead log files that
the server recovers a first state from. Also the reader of those files, for
the comparison at the end of a run.

The log's layout is the program's (`<wal_dir>/<quoted name>/<index>.wal`
and the commit journal beside them, records `[u32 crc32][u32 length][u8
type][payload]`, the checksum over length, type and payload); the code here
is a copy of that layout, not an import, so that a later change to the
program's writer cannot move the yardstick with it.
"""

from __future__ import annotations

import os
import random
import struct
import zlib
from urllib.parse import quote

ALPHABET = "etaoinshrdlucmfwypvbgkqjxz"
TEXT_TYPE = "body"
REC_UPDATE, REC_JOURNAL_ENTRY = 1, 3
_HEADER = struct.Struct("<IIB")


def first_texts(seed: int, docs: int, units: int) -> "list[str]":
    """`docs` texts of `units` ASCII letters and spaces each, all different:
    a window into one pool of seeded text, stamped with the document's number."""
    rng = random.Random(seed ^ 0x7E87)
    pool = "".join(rng.choices(ALPHABET + "    ", k=2 * units + 64))
    texts = []
    for doc in range(docs):
        stamp = f"{doc:06d} "[:units]
        at = rng.randrange(units + 64)
        texts.append(stamp + pool[at : at + units - len(stamp)])
    return texts


def first_client(seed: int, doc: int) -> int:
    """The client id that 'typed' a document's first text: below 2**30, so
    it never meets a live client's id (those have bit 30 set)."""
    return (seed * 1_000_003 + doc * 7919 + 17) % (1 << 30)


def _varuint(value: int) -> bytes:
    out = bytearray()
    while value > 0x7F:
        out.append(0x80 | (value & 0x7F))
        value >>= 7
    out.append(value)
    return bytes(out)


def _varstring(text: str) -> bytes:
    raw = text.encode("utf-8")
    return _varuint(len(raw)) + raw


def text_update(client: int, text: str) -> bytes:
    """The Yjs v1 update in which `client` inserts `text`, as one string
    item at clock 0 with no origins, into the shared text TEXT_TYPE."""
    return b"".join(
        (
            _varuint(1),  # one client's structs
            _varuint(1),  # one struct
            _varuint(client),
            _varuint(0),  # from clock 0
            bytes([4]),  # a string item, no origins
            _varuint(1),  # its parent is a named shared type
            _varstring(TEXT_TYPE),
            _varstring(text),
            _varuint(0),  # an empty delete set
        )
    )


def wal_record(payload: bytes, rec_type: int = REC_UPDATE) -> bytes:
    body = struct.pack("<IB", len(payload), rec_type) + payload
    return struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF) + body


def doc_dir(wal_dir: str, name: str) -> str:
    return os.path.join(wal_dir, quote(name, safe=""))


def write_wal(wal_dir: str, names: "list[str]", updates: "list[list[bytes]]") -> int:
    """One log segment per document, holding its first updates, a record
    each. Returns the bytes written."""
    written = 0
    for name, payloads in zip(names, updates):
        directory = doc_dir(wal_dir, name)
        os.mkdir(directory)
        records = b"".join(wal_record(payload) for payload in payloads)
        with open(os.path.join(directory, "00000000.wal"), "wb") as fh:
            fh.write(records)
        written += len(records)
    return written


def _records(data: bytes) -> "list[tuple[int, bytes]]":
    """The whole records at the head of a log file: (type, payload). Stops
    at the first frame that is short or fails its checksum."""
    records = []
    at = 0
    while at + _HEADER.size <= len(data):
        crc, length, rec_type = _HEADER.unpack_from(data, at)
        end = at + _HEADER.size + length
        if end > len(data) or zlib.crc32(data[at + 4 : end]) & 0xFFFFFFFF != crc:
            break
        records.append((rec_type, data[at + _HEADER.size : end]))
        at = end
    return records


def _files(directory: str, ending: str) -> "list[bytes]":
    try:
        entries = sorted(e for e in os.listdir(directory) if e.endswith(ending))
    except FileNotFoundError:
        return []
    contents = []
    for entry in entries:
        with open(os.path.join(directory, entry), "rb") as fh:
            contents.append(fh.read())
    return contents


def read_wal(wal_dir: str, names: "list[str]") -> "dict[str, list[bytes]]":
    """What a recovery would replay for each document, as the disk holds it
    now: the payloads of its own segments, then its entries in the shared
    commit journal (`journal%/*.journal`, records of type 3 that wrap a
    document's name and an inner record). An entry may be in both."""
    journal: "dict[str, list[bytes]]" = {}
    for data in _files(os.path.join(wal_dir, "journal%"), ".journal"):
        for rec_type, payload in _records(data):
            if rec_type == REC_JOURNAL_ENTRY:
                name_bytes, _inner_type = struct.unpack_from("<HB", payload, 0)
                name = payload[3 : 3 + name_bytes].decode("utf-8")
                journal.setdefault(name, []).append(payload[3 + name_bytes :])
    return {
        name: [
            payload
            for data in _files(doc_dir(wal_dir, name), ".wal")
            for _type, payload in _records(data)
        ]
        + journal.get(name, [])
        for name in names
    }
