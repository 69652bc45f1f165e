"""The plain reference: what a shared text has to read once every client's
updates are merged.

It imports nothing of the program. It reads the Yjs v1 update bytes that
the clients put on the wire (the requests), and merges them with a
list-per-unit YATA: every UTF-16 unit is one entry with its id, its left
and right origin and a tombstone. Slow and obvious on purpose.

The traffic is ASCII text in one shared type, so a string's length is its
unit count and every struct is a string item; anything else in an update
is an error, not a guess.

`client_before` is the tie-break between two inserts with the same left
origin. Yjs orders them by the client id as an unsigned number. The
controls of `compare.py` pass a broken one.
"""

from __future__ import annotations

_KEY_SHIFT = 40  # a unit's key: client << 40 | clock (clocks stay far below 2**40)
_NONE = -1


def unsigned_before(a: int, b: int) -> bool:
    return a < b


def signed32_before(a: int, b: int) -> bool:
    """The broken tie-break of the `signed-client-order` control: client
    ids compared as int32, so ids from 2**31 up sort first."""
    return (a - (1 << 32) if a >= 1 << 31 else a) < (b - (1 << 32) if b >= 1 << 31 else b)


class _Reader:
    def __init__(self, data: bytes) -> None:
        self.data = data
        self.at = 0

    def uint8(self) -> int:
        value = self.data[self.at]
        self.at += 1
        return value

    def varuint(self) -> int:
        value = shift = 0
        while True:
            byte = self.data[self.at]
            self.at += 1
            value |= (byte & 0x7F) << shift
            if byte < 0x80:
                return value
            shift += 7

    def varstring(self) -> str:
        size = self.varuint()
        raw = self.data[self.at : self.at + size]
        if len(raw) != size:
            raise ValueError("update ends inside a string")
        self.at += size
        return raw.decode("utf-8")

    def id(self) -> "tuple[int, int]":
        return self.varuint(), self.varuint()


def decode_update(data: bytes) -> "tuple[list, list]":
    """(inserts, deletes) of one Yjs v1 update.

    inserts: (client, clock, left origin id or None, right origin id or
    None, text); deletes: (client, clock, length)."""
    reader = _Reader(data)
    inserts: list = []
    for _ in range(reader.varuint()):
        structs = reader.varuint()
        client = reader.varuint()
        clock = reader.varuint()
        for _ in range(structs):
            info = reader.uint8()
            kind = info & 0x1F
            if kind == 10:  # a gap in the clocks: nothing to merge
                clock += reader.varuint()
                continue
            if kind != 4:
                raise ValueError(f"struct kind {kind} is not a string item")
            left = reader.id() if info & 0x80 else None
            right = reader.id() if info & 0x40 else None
            if left is None and right is None:
                if reader.varuint() == 1:
                    reader.varstring()  # the shared type's name
                else:
                    reader.id()
            if info & 0x20:
                raise ValueError("map entries are not text")
            text = reader.varstring()
            if not text.isascii():
                raise ValueError("the reference counts ASCII units only")
            inserts.append((client, clock, left, right, text))
            clock += len(text)
    deletes: list = []
    for _ in range(reader.varuint()):
        client = reader.varuint()
        for _ in range(reader.varuint()):
            clock = reader.varuint()
            deletes.append((client, clock, reader.varuint()))
    if reader.at != len(data):
        raise ValueError("bytes left over after the delete set")
    return inserts, deletes


def _key(unit_id) -> int:
    return _NONE if unit_id is None else unit_id[0] << _KEY_SHIFT | unit_id[1]


class ReferenceText:
    """One shared text, merged from updates in whatever order they come."""

    def __init__(self, client_before=unsigned_before) -> None:
        self.client_before = client_before
        self.keys: "list[int]" = []
        self.chars: "list[str]" = []
        self.lefts: "list[int]" = []  # key of each unit's left origin
        self.rights: "list[int]" = []
        self.deleted: "set[int]" = set()
        self.clocks: "dict[int, int]" = {}

    def apply_update(self, data: bytes) -> None:
        self.apply_updates([data])

    def apply_updates(self, updates) -> None:
        """Merge updates that may come in any order and more than once (a
        log replayed on top of a snapshot does both): an insert waits until
        what it leans on is there, and what is there already is skipped."""
        waiting: list = []
        for data in updates:
            inserts, deletes = decode_update(data)
            waiting.extend(inserts)
            for client, clock, length in deletes:
                base = client << _KEY_SHIFT | clock
                self.deleted.update(range(base, base + length))
        while waiting:
            still = [insert for insert in waiting if not self._insert_if_ready(*insert)]
            if len(still) == len(waiting):
                raise ValueError(f"{len(still)} inserts lean on units that no update brings")
            waiting = still

    def _known(self, unit_id) -> bool:
        return unit_id is None or self.clocks.get(unit_id[0], 0) > unit_id[1]

    def _insert_if_ready(self, client: int, clock: int, left, right, text: str) -> bool:
        have = self.clocks.get(client, 0)
        if clock + len(text) <= have:
            return True  # all of it is here already
        if clock > have or not (self._known(left) and self._known(right)):
            return False
        if clock < have:  # its first units are here: the rest leans on the last of them
            text, left, clock = text[have - clock :], (client, have - 1), have
        self.insert(client, clock, left, right, text)
        return True

    def insert(self, client: int, clock: int, left, right, text: str) -> None:
        have = self.clocks.get(client, 0)
        if clock != have:
            raise ValueError(f"client {client}: clock {clock} after {have}")
        keys = self.keys
        left_key, right_key = _key(left), _key(right)
        at = keys.index(left_key) if left is not None else -1
        end = keys.index(right_key) if right is not None else len(keys)
        scan = at + 1
        before: "set[int]" = set()
        conflicting: "set[int]" = set()
        while scan < end:
            other = keys[scan]
            before.add(other)
            conflicting.add(other)
            other_left = self.lefts[scan]
            if other_left == left_key:
                if self.client_before(other >> _KEY_SHIFT, client):
                    at = scan
                    conflicting.clear()
                elif self.rights[scan] == right_key:
                    break
            elif other_left != _NONE and other_left in before:
                if other_left not in conflicting:
                    at = scan
                    conflicting.clear()
            else:
                break
            scan += 1
        base = client << _KEY_SHIFT | clock
        new = list(range(base, base + len(text)))
        at += 1
        keys[at:at] = new
        self.chars[at:at] = text
        # a run is its first unit followed by units that each lean on the one before
        self.lefts[at:at] = [left_key] + new[:-1]
        self.rights[at:at] = [right_key] * len(new)
        self.clocks[client] = clock + len(text)

    def text(self) -> str:
        deleted = self.deleted
        if not deleted:
            return "".join(self.chars)
        return "".join(c for k, c in zip(self.keys, self.chars) if k not in deleted)

    def state_vector(self) -> "dict[int, int]":
        return dict(self.clocks)
