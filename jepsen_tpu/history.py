"""Operation histories: schema, canonicalisation, columnar encoding.

The history is the framework's central artifact: an ordered list of ops

    {:index i, :time nanos, :process p, :type t, :f f, :value v}

exactly the schema the reference produces (op contract documented at
jepsen/src/jepsen/generator.clj:371-380 and knossos.history, used by
jepsen/src/jepsen/core.clj:230 `history/index`). Types:

    invoke  a client begins an operation
    ok      it completed and took effect
    fail    it completed and did NOT take effect
    info    indeterminate (crashed) — may or may not have taken effect;
            the process is dead and its op stays concurrent with
            everything after it (knossos crash semantics)

This module provides:
  * `Op` — a dict with attribute access (op.type, op["type"] both work),
  * `History` — a list of ops + canonicalisation (index/pair/complete,
    the knossos.history equivalents) and EDN/JSONL IO,
  * `calls()` — invocation/completion pairing into `Call` records, the
    input to linearizability checking,
  * `Columns` — struct-of-arrays encoding with interning tables, the
    host↔device boundary: everything past this point is integer arrays.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from itertools import compress, repeat
from typing import Any, Callable, Iterable, Iterator, Optional

import numpy as np

from jepsen_tpu import edn
from jepsen_tpu.edn import Keyword

TYPES = ("invoke", "ok", "fail", "info")
_TYPE_CODE = {t: i for i, t in enumerate(TYPES)}
NEMESIS = "nemesis"  # the nemesis pseudo-process
NEMESIS_CODE = -2  # integer encoding of :nemesis in columnar form


class Op(dict):
    """An operation: a dict with attribute sugar.

    Extra keys (:error, :debug, anything a client attaches) ride along,
    matching the reference's open-map ops.
    """

    __slots__ = ()

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError:
            if k in ("index", "time", "process", "type", "f", "value", "error"):
                return None
            raise AttributeError(k)

    def __setattr__(self, k, v):
        self[k] = v

    # -- predicates (knossos.op equivalents: invoke?/ok?/fail?/info?,
    #    used pervasively e.g. jepsen/src/jepsen/checker.clj:154-156)
    @property
    def is_invoke(self):
        return self.get("type") == "invoke"

    @property
    def is_ok(self):
        return self.get("type") == "ok"

    @property
    def is_fail(self):
        return self.get("type") == "fail"

    @property
    def is_info(self):
        return self.get("type") == "info"

    def __repr__(self):
        core = {k: self.get(k) for k in ("index", "type", "process", "f", "value")
                if k in self}
        extra = {k: v for k, v in self.items() if k not in core}
        core.update(extra)
        inner = ", ".join(f"{k}={v!r}" for k, v in core.items())
        return f"Op({inner})"


def op(type=None, process=None, f=None, value=None, **kw) -> Op:
    """Construct an Op. `op('invoke', 0, 'read', None)`."""
    o = Op(kw)
    if type is not None:
        o["type"] = type
    if process is not None:
        o["process"] = process
    if f is not None:
        o["f"] = f
    o["value"] = value
    return o


# Test-fixture constructors (knossos.core/invoke-op, ok-op, fail-op —
# used by the reference's checker tests, jepsen/test/jepsen/checker_test.clj:7)
def invoke_op(process, f, value, **kw) -> Op:
    return op("invoke", process, f, value, **kw)


def ok_op(process, f, value, **kw) -> Op:
    return op("ok", process, f, value, **kw)


def fail_op(process, f, value, **kw) -> Op:
    return op("fail", process, f, value, **kw)


def info_op(process, f, value, **kw) -> Op:
    return op("info", process, f, value, **kw)


# --------------------------------------------------------------- conversion


def _from_edn(x: Any) -> Any:
    """EDN values -> plain Python. Keywords become strings."""
    if isinstance(x, Keyword):
        return x.name
    if isinstance(x, list):
        return [_from_edn(e) for e in x]
    if isinstance(x, tuple):
        return tuple(_from_edn(e) for e in x)
    if isinstance(x, dict):
        return {_from_edn(k): _from_edn(v) for k, v in x.items()}
    if isinstance(x, frozenset):
        return frozenset(_from_edn(e) for e in x)
    return x


def op_from_edn(form: dict) -> Op:
    return Op(_from_edn(form))


def _to_edn(x: Any) -> Any:
    if isinstance(x, str):
        return Keyword(x)
    return x


def op_to_edn_str(o: Op) -> str:
    """Render an op as the reference's EDN map (keyword keys; keyword-ish
    string values for :type/:f/:process where the reference uses keywords)."""
    parts = []
    for k, v in o.items():
        parts.append(":" + str(k))
        if k in ("type", "f") and isinstance(v, str):
            parts.append(":" + v)
        elif k == "process" and v == NEMESIS:
            parts.append(":nemesis")
        else:
            parts.append(edn.dumps(v))
    return "{" + ", ".join(
        f"{parts[i]} {parts[i+1]}" for i in range(0, len(parts), 2)
    ) + "}"


class History(list):
    """A list of `Op` with canonicalisation and IO helpers."""

    # ------------------------------------------------------------- creation
    @classmethod
    def wrap(cls, ops: Iterable) -> "History":
        h = cls()
        for o in ops:
            h.append(o if isinstance(o, Op) else Op(o))
        return h

    # ----------------------------------------------------------------- IO
    @classmethod
    def from_edn(cls, text: str) -> "History":
        """Parse a reference-format history.edn (one op map per line, as
        written by jepsen/src/jepsen/store.clj:351-362)."""
        return cls.wrap(op_from_edn(f) for f in edn.iter_forms(text))

    @classmethod
    def load(cls, path: str) -> "History":
        with open(path) as fh:
            text = fh.read()
        if path.endswith(".jsonl"):
            return cls.wrap(Op(json.loads(line)) for line in text.splitlines() if line.strip())
        return cls.from_edn(text)

    def to_edn(self) -> str:
        return "\n".join(op_to_edn_str(o) for o in self) + "\n"

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(o, default=_json_default) for o in self) + "\n"

    def save(self, path: str):
        with open(path, "w") as fh:
            fh.write(self.to_jsonl() if path.endswith(".jsonl") else self.to_edn())

    def save_npz(self, path: str) -> str:
        """Columnar binary sidecar — the Fressian-parity fast reload
        (the reference stores binary history for exactly this,
        jepsen/src/jepsen/store.clj:31-116; ours is struct-of-arrays,
        the layout the device engines consume). Exact by construction:
        the canonical columns are serialized, decoded back, and every
        op diffed against its reconstruction; any mismatch (op with
        extra keys, exotic process, lossy value round-trip) rides as a
        full EDN override line. Checker histories reconstruct fully, so
        reload is numpy-speed with zero EDN parsing."""
        if not path.endswith(".npz"):
            path = path + ".npz"
        cols = self.columns()
        f_ser = [edn.dumps(v) for v in cols.f_table._values]
        v_ser = [edn.dumps(v) for v in cols.value_table._values]
        f_dec = _decode_table(f_ser)
        v_dec = _decode_table(v_ser)
        ov_idx: list = []
        ov_edn: list = []
        for i, o in enumerate(self):
            recon = _op_from_columns(i, cols.index, cols.time,
                                     cols.process, cols.type, cols.f,
                                     cols.value, f_dec, v_dec)
            if dict(recon) != dict(o):
                ov_idx.append(i)
                ov_edn.append(op_to_edn_str(o))
        np.savez_compressed(
            path,
            version=np.int64(NPZ_VERSION),
            index=cols.index, time=cols.time, process=cols.process,
            type=cols.type, f=cols.f, value=cols.value,
            f_table=np.array(f_ser, dtype="U") if f_ser
            else np.zeros(0, "U1"),
            value_table=np.array(v_ser, dtype="U") if v_ser
            else np.zeros(0, "U1"),
            override_idx=np.array(ov_idx, np.int64),
            override_edn=np.array(ov_edn, dtype="U") if ov_edn
            else np.zeros(0, "U1"))
        return path

    @classmethod
    def load_npz(cls, path: str) -> "History":
        """Reload a save_npz sidecar. Exact: columnar reconstruction
        plus the stored EDN override lines."""
        z = np.load(path, allow_pickle=False)
        v = int(z["version"])
        if v > NPZ_VERSION:
            raise ValueError(f"history npz version {v} is newer than "
                             f"this reader ({NPZ_VERSION})")
        f_dec = _decode_table(z["f_table"])
        v_dec = _decode_table(z["value_table"])
        index, time, process = z["index"], z["time"], z["process"]
        type_, f, value = z["type"], z["f"], z["value"]
        ops = [_op_from_columns(i, index, time, process, type_, f,
                                value, f_dec, v_dec)
               for i in range(len(index))]
        for i, s in zip(z["override_idx"].tolist(), z["override_edn"]):
            ops[i] = op_from_edn(edn.loads(str(s)))
        return cls.wrap(ops)

    # --------------------------------------------------------- canonicalise
    def index(self) -> "History":
        """Assign :index 0..n-1 in order (knossos.history/index, called at
        jepsen/src/jepsen/core.clj:230 before any checker runs)."""
        for i, o in enumerate(self):
            o["index"] = i
        return self

    def pairs(self) -> "History":
        """Pair invocations with completions: each op gets a :pair-index
        pointing at its counterpart (completion of the same process), or -1
        for unpaired ops (knossos.history pairing semantics).

        A process executes at most one op at a time, so matching is by
        process: an invoke pairs with the next ok/fail/info of the same
        process. Nemesis ops pair the same way.
        """
        if any(o.get("index") is None for o in self):
            self.index()
        open_by_process: dict = {}
        for o in self:
            p = o.get("process")
            if o.is_invoke:
                o["pair-index"] = -1
                open_by_process[p] = o
            else:
                inv = open_by_process.pop(p, None)
                if inv is not None:
                    inv["pair-index"] = o["index"]
                    o["pair-index"] = inv["index"]
                else:
                    o["pair-index"] = -1
        return self

    def complete(self) -> "History":
        """knossos.history/complete semantics (used by the reference at
        jepsen/src/jepsen/checker.clj:756 and checker/timeline.clj:172):
        fill each invocation's :value from its ok completion when the
        invocation's value is nil (reads learn their value at completion).
        """
        self.pairs()
        by_index = {o["index"]: o for o in self if o.get("index") is not None}
        for o in self:
            if o.is_invoke and o.get("pair-index", -1) >= 0:
                comp = by_index[o["pair-index"]]
                if comp.is_ok and o.get("value") is None:
                    o["value"] = comp.get("value")
        return self

    # ------------------------------------------------------------- queries
    def invocations(self) -> Iterator[Op]:
        return (o for o in self if o.is_invoke)

    def completions(self) -> Iterator[Op]:
        return (o for o in self if not o.is_invoke)

    def oks(self) -> Iterator[Op]:
        return (o for o in self if o.is_ok)

    def client_ops(self) -> "History":
        return History.wrap(o for o in self if isinstance(o.get("process"), int))

    def processes(self) -> list:
        seen, out = set(), []
        for o in self:
            p = o.get("process")
            if p not in seen:
                seen.add(p)
                out.append(p)
        return out

    def filter_f(self, *fs) -> "History":
        fset = set(fs)
        return History.wrap(o for o in self if o.get("f") in fset)

    # ------------------------------------------------------------ columnar
    def columns(self, value_encoder: Optional[Callable] = None) -> "Columns":
        return Columns.from_history(self, value_encoder)


def _json_default(x):
    if isinstance(x, frozenset):
        return sorted(x, key=repr)
    return str(x)


# ------------------------------------------------------------------- Calls


@dataclass
class Call:
    """An invocation/completion pair — the unit of linearizability checking.

    crashed=True means the completion was :info (or missing): the op may or
    may not have taken effect and stays concurrent with the rest of the
    history (knossos crash semantics — SURVEY.md §7.3 hard part #2).
    """

    index: int          # dense call id, 0..m-1 in invocation order
    process: Any
    f: str
    value: Any          # invocation value (args)
    result: Any         # completion value (None if crashed)
    invoke_index: int   # position of invocation in the history
    complete_index: int # position of completion; crashed -> len(history)
    crashed: bool

    def __repr__(self):
        tail = " CRASHED" if self.crashed else f" -> {self.result!r}"
        return f"Call#{self.index}(p{self.process} {self.f} {self.value!r}{tail})"


def calls(history: History, drop_failed: bool = True) -> list:
    """Pair invocations with completions into Call records.

    With drop_failed (the default), failed ops are dropped — they did not
    take effect (knossos `without-failures` preprocessing); otherwise they
    are kept as completed calls. Nemesis and non-client ops (any process
    that is not an int; client process ids fit an int64) are skipped. Crashed (:info) calls get
    complete_index = len(history).

    Pairing is columnar: the client ops are stably sorted by process,
    and a completion pairs with the op just before it in its process's
    group when that op is an invoke. That is the one-open-op-per-process
    rule exactly: an invoke followed by another invoke of the same
    process stays open (crashed), and a completion with no open invoke
    is ignored. Each column is read once per op, and the Call records
    are built positionally, in field order, in one pass.
    """
    n = len(history)

    def col(key, ops=history):
        return map(dict.get, ops, repeat(key))

    procs = list(col("process"))
    client = np.fromiter(map(isinstance, procs, repeat(int)), bool, n)
    pos = np.flatnonzero(client)
    if not len(pos):
        return []
    types = np.fromiter(col("type"), object, n)
    proc = np.fromiter(compress(procs, client), np.int64, len(pos))
    by_proc = np.argsort(proc, kind="stable")
    order, sp = pos[by_proc], proc[by_proc]  # history positions by process
    si = types[order] == "invoke"
    paired = np.flatnonzero((sp[1:] == sp[:-1]) & si[:-1] & ~si[1:]) + 1
    comp = np.full(n, -1, np.int64)  # each invoke's completion, or -1
    comp[order[paired - 1]] = order[paired]

    inv = pos[types[pos] == "invoke"]
    k = comp[inv]
    ok = (k >= 0) & (types[k] == "ok")
    done = ok | ((k >= 0) & (types[k] == "fail"))
    if drop_failed:
        keep = ~done | ok
        inv, k, ok, done = inv[keep], k[keep], ok[keep], done[keep]
    values = list(col("value")) + [None]  # values[n]: no ok completion
    inv_l = inv.tolist()
    return list(map(
        Call, range(len(inv_l)), map(procs.__getitem__, inv_l),
        col("f", map(history.__getitem__, inv_l)),
        map(values.__getitem__, inv_l),
        map(values.__getitem__, np.where(ok, k, n).tolist()),
        inv_l, np.where(done, k, n).tolist(), (~done).tolist()))


def prune_wildcard_calls(cs: list) -> list:
    """Drop calls that cannot constrain a linearizability search: crashed
    reads. A crashed read's value is unknown, so its model step is the
    identity and always succeeds — it may be linearized at any point or
    never, and removing it is sound. This avoids exponential blowup from
    forever-open crashed calls (each open crashed call doubles the
    frontier's mask space; cf. the reference's tractability caps,
    jepsen/src/jepsen/tests/linearizable_register.clj:30-32). Crashed
    mutating ops (writes, cas, acquire/release, dequeue) must stay — even
    value-less ones mutate state. Re-numbers the surviving dense indices."""
    out = [c for c in cs if not (c.crashed and c.f == "read")]
    for j, c in enumerate(out):
        c.index = j
    return out


# ---------------------------------------------------------------- Columns


class Intern:
    """Bidirectional value <-> int table. nil is always code -1."""

    def __init__(self):
        self._to_code: dict = {}
        self._values: list = []

    def code(self, v) -> int:
        if v is None:
            return -1
        key = _hashable(v)
        c = self._to_code.get(key)
        if c is None:
            c = len(self._values)
            self._to_code[key] = c
            self._values.append(v)
        return c

    def value(self, code: int):
        return None if code < 0 else self._values[code]

    def __len__(self):
        return len(self._values)


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(e) for e in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    if isinstance(v, set):
        return frozenset(_hashable(e) for e in v)
    return v


@dataclass
class Columns:
    """Struct-of-arrays history encoding — the host↔device boundary.

    Every field is a dense numpy array over ops, with interning tables
    mapping :f and :value back to Python objects. This is what ships to
    the TPU engine (jepsen_tpu.parallel.engine); nothing past this point
    touches Python objects. Replaces the reference's per-op persistent
    maps with a layout XLA can tile.
    """

    index: np.ndarray      # i64
    time: np.ndarray       # i64 nanos (-1 if absent)
    process: np.ndarray    # i32; :nemesis -> -2, other non-ints -> -3
    type: np.ndarray       # u8, code into TYPES
    f: np.ndarray          # i32 into f_table
    value: np.ndarray      # i32 into value_table (-1 = nil / unencodable)
    f_table: Intern = field(default_factory=Intern)
    value_table: Intern = field(default_factory=Intern)

    @classmethod
    def from_history(cls, h: History, value_encoder: Optional[Callable] = None):
        n = len(h)
        idx = np.empty(n, np.int64)
        tim = np.empty(n, np.int64)
        proc = np.empty(n, np.int32)
        typ = np.empty(n, np.uint8)
        fcol = np.empty(n, np.int32)
        val = np.empty(n, np.int32)
        ftab, vtab = Intern(), Intern()
        enc = value_encoder or (lambda v: vtab.code(v))
        for i, o in enumerate(h):
            idx[i] = o.get("index", i)
            tim[i] = o.get("time", -1) if o.get("time") is not None else -1
            p = o.get("process")
            proc[i] = p if isinstance(p, int) else (NEMESIS_CODE if p == NEMESIS else -3)
            typ[i] = _TYPE_CODE.get(o.get("type"), 255)
            fcol[i] = ftab.code(o.get("f"))
            val[i] = enc(o.get("value"))
        return cls(idx, tim, proc, typ, fcol, val, ftab, vtab)

    def __len__(self):
        return len(self.index)


# -------------------------------------------------- columnar npz sidecar

NPZ_VERSION = 1


def _op_from_columns(i: int, index, time, process, type_, f, value,
                     f_vals: list, v_vals: list) -> Op:
    """Reconstruct op i from columnar arrays + decoded intern tables.
    The single source of truth for the npz round-trip: save() diffs
    this reconstruction against the original op and stores an EDN
    override line when they differ, so load() is exact regardless of
    what the columns can or cannot express."""
    o: dict = {"index": int(index[i])}
    t = int(time[i])
    if t != -1:
        o["time"] = t
    p = int(process[i])
    if p >= 0:
        o["process"] = p
    elif p == NEMESIS_CODE:
        o["process"] = NEMESIS
    tc = int(type_[i])
    if tc < len(TYPES):
        o["type"] = TYPES[tc]
    fv = f_vals[int(f[i])] if int(f[i]) >= 0 else None
    if fv is not None:
        o["f"] = fv
    vc = int(value[i])
    o["value"] = v_vals[vc] if vc >= 0 else None
    return Op(o)


def _decode_table(serialized) -> list:
    return [_from_edn(edn.loads(str(s))) for s in serialized]
