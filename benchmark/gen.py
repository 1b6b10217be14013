"""Seeded trace traffic as plain columns, and the frames sent from them.

The trace shapes are those of the program's ``pdata/gen.py``
(``synthesize_traces`` + ``inject_faults``: an otel-demo style service
mesh, about ten spans a trace, four fault kinds), rebuilt here so that
the yardstick owns its traffic. Two generators draw a pool:

* the **shape** generator (``shape_seed``, from the traffic file) decides
  every size: each trace's root service (which fixes its tree), which
  traces carry which fault, which subtree a ``missing_subtree`` fault
  removes, the order of the traces over the pool and each trace's id
  (the program packs a call's traces into rows in the order of their
  ids). Every ``--seed`` therefore offers the same frames of the same
  trace sizes in the same places, and they pack to the same rows, so
  the seed does not change the work (while the seed ordered the traces
  and drew the ids, a frame held 2,305 to 3,002 spans by the seed,
  eleven of them packed to 490 to 513 rows of a 512-row call, and a
  seed read up to 3% apart from another and the same twice: PERF.md,
  PR 27);
* the **value** generator (``--seed``) decides everything else: every
  latency, gap and error flag, the size of each fault, and (in
  ``loadgen``) the pool frame the sending starts at.

A pool frame is plain numpy columns plus a string table (``PlainFrame``):
the reference featurizes from those and never sees a program object. The
same columns are turned once into the program's client-side request type
(``SpanBatch``) and re-keyed per send with fresh ids (a numpy add).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

TOPOLOGY: dict[str, list[tuple[str, str]]] = {
    "frontend": [("cart", "GET /cart"), ("product", "GET /products"),
                 ("recommendation", "GET /recommend"), ("ad", "GET /ads")],
    "cart": [("redis", "HGETALL cart")],
    "product": [("postgres", "SELECT products")],
    "recommendation": [("product", "GET /products")],
    "ad": [],
    "checkout": [("cart", "GET /cart"), ("payment", "POST /charge"),
                 ("shipping", "POST /ship"), ("email", "POST /send")],
    "payment": [],
    "shipping": [("postgres", "SELECT rates")],
    "email": [],
    "currency": [],
    "redis": [],
    "postgres": [],
}
ROOT_SERVICES = ("frontend", "checkout", "currency")
BASE_LATENCY_US = {
    "frontend": 800.0, "cart": 300.0, "product": 400.0,
    "recommendation": 350.0, "ad": 150.0, "checkout": 900.0,
    "payment": 1200.0, "shipping": 500.0, "email": 250.0, "currency": 80.0,
    "redis": 60.0, "postgres": 450.0,
}
LATENCY_SIGMA = 0.35
FAULT_KINDS = ("latency_spike", "error_storm", "slow_dependency",
               "missing_subtree")
KIND_SERVER, KIND_CLIENT = 2, 3
STATUS_UNSET, STATUS_ERROR = 0, 2
START_UNIX_NANO = 1_700_000_000_000_000_000
# ids of a sent frame: local id + serial * SERIAL_STRIDE (local ids stay
# far below the stride, so ids never collide across frames)
SERIAL_STRIDE = 1 << 24


@dataclass
class PlainFrame:
    """One pool frame as plain columns (length n spans, in sent order)."""

    strings: list[str]
    trace: np.ndarray       # int64 trace ordinal within the frame
    trace_lo: np.ndarray    # uint64 low half of the trace id
    span_id: np.ndarray     # uint64 local span id, unique in the frame
    parent: np.ndarray      # uint64 local parent span id, 0 = root
    service: np.ndarray     # int32 index into strings
    name: np.ndarray        # int32 index into strings
    kind: np.ndarray        # int8
    status: np.ndarray      # int8
    start: np.ndarray       # uint64 ns
    end: np.ndarray         # uint64 ns
    attrs: list[dict]       # per-span attributes as sent

    def __len__(self) -> int:
        return int(self.span_id.shape[0])


def _tree(service: str, op: str, max_depth: int) -> list[tuple]:
    """The span tree a request to ``service`` makes, post-order as the
    program's generator emits it: (service, op, kind, parent index,
    callee-or-None). Index -1 is "no parent"."""
    out: list[list] = []

    def walk(svc: str, op_: str, parent: int, depth: int) -> int:
        me = len(out)
        out.append([svc, op_, KIND_SERVER, parent, None])
        if depth < max_depth:
            for child_svc, child_op in TOPOLOGY.get(svc, ()):
                client = len(out)
                out.append([svc, child_op, KIND_CLIENT, me, child_svc])
                walk(child_svc, child_op, client, depth + 1)
        return me

    walk(service, op, -1, 0)
    return [tuple(x) for x in out]


def _emit_trace(rv: np.random.Generator, tree: list[tuple], clock: int,
                error_rate: float) -> tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """Start, end and status of every node of one trace: the timing
    rules of the program's ``_emit_span``, over a ready tree."""
    n = len(tree)
    start = np.zeros(n, np.int64)
    end = np.zeros(n, np.int64)
    status = np.zeros(n, np.int8)
    children: list[list[int]] = [[] for _ in range(n)]
    for i, node in enumerate(tree):
        if node[3] >= 0:
            children[node[3]].append(i)

    def server(i: int, start_ns: int) -> int:
        svc = tree[i][0]
        self_ns = int(rv.lognormal(np.log(BASE_LATENCY_US.get(svc, 200.0)),
                                   LATENCY_SIGMA) * 1_000)
        cursor = start_ns + self_ns // 2
        for c in children[i]:                      # client spans
            child_start = cursor + int(rv.integers(5_000, 40_000))
            callee = children[c][0]
            child_end = server(
                callee, child_start + int(rv.integers(2_000, 20_000)))
            client_end = child_end + int(rv.integers(2_000, 20_000))
            start[c], end[c] = child_start, client_end
            cursor = client_end
        start[i] = start_ns
        end[i] = max(cursor, start_ns + self_ns)
        if rv.random() < error_rate:
            status[i] = STATUS_ERROR
        return int(end[i])

    server(0, clock)
    return start, end, status


def _apply_fault(rv: np.random.Generator, kind: str, victim: int,
                 tree: list[tuple], start: np.ndarray, end: np.ndarray,
                 status: np.ndarray) -> np.ndarray:
    """One fault on one trace, after the program's ``inject_faults``;
    returns the keep-mask (``missing_subtree`` removes spans). ``victim``
    comes from the shape generator, magnitudes from the value one."""
    n = len(tree)
    keep = np.ones(n, bool)
    parent = [node[3] for node in tree]

    def ancestors(i: int) -> list[int]:
        out = []
        while parent[i] >= 0:
            i = parent[i]
            out.append(i)
        return out

    def subtree(i: int) -> list[int]:
        out, stack = [], [i]
        while stack:
            r = stack.pop()
            out.append(r)
            stack.extend(j for j in range(n) if parent[j] == r)
        return out

    if kind == "latency_spike":
        end[victim] += int((end[victim] - start[victim])
                           * rv.uniform(8.0, 30.0))
        for a in ancestors(victim):
            end[a] = max(int(end[a]), int(end[victim])) + 1_000
    elif kind == "error_storm":
        status[subtree(victim)] = STATUS_ERROR
    elif kind == "slow_dependency":
        rows = [i for i in range(n) if tree[i][0] == tree[victim][0]]
        factor = rv.uniform(5.0, 15.0)
        for r in rows:
            end[r] = start[r] + int((end[r] - start[r]) * factor)
        for r in rows:
            for a in ancestors(r):
                end[a] = max(int(end[a]), int(end[r]) + 1_000)
    elif kind == "missing_subtree":
        keep[subtree(victim)] = False
        caller = parent[victim]
        end[caller] = start[caller] + 1_000
    else:
        raise ValueError(f"unknown fault kind {kind!r}")
    return keep


def make_pool(traffic: dict[str, Any], seed: int) -> list[PlainFrame]:
    """The cell's pool of distinct frames, from the traffic file's
    parameters and ``--seed`` (see the module docstring for what each of
    the two generators decides)."""
    n_frames = int(traffic["pool_frames"])
    per_frame = int(traffic["traces_per_frame"])
    max_depth = int(traffic.get("max_depth", 6))
    error_rate = float(traffic.get("error_rate", 0.005))
    rs = np.random.default_rng(int(traffic.get("shape_seed", 1)))
    rv = np.random.default_rng(int(seed))
    trees = {svc: _tree(svc, f"GET /{svc}", max_depth)
             for svc in ROOT_SERVICES}

    # ---- shapes: root service and fault of every trace of the pool
    total = n_frames * per_frame
    roots = rs.integers(len(ROOT_SERVICES), size=total)
    fault_kind = np.full(total, -1)
    fault_victim = np.zeros(total, np.int64)
    faulty_frames = max(0, int(round(n_frames
                                     * float(traffic.get("fault_frame_share",
                                                         0.0)))))
    per_faulty = int(round(per_frame
                           * float(traffic.get("fault_trace_share", 0.0))))
    slots = rs.permutation(total)[:faulty_frames * per_faulty]
    for t in slots:
        tree = trees[ROOT_SERVICES[roots[t]]]
        kind = int(rs.integers(len(FAULT_KINDS)))
        victim = int(rs.integers(len(tree)))
        if FAULT_KINDS[kind] == "missing_subtree":
            # a victim that has a parent and children, as the program's
            # injector requires; none (single-span trace) = no fault
            ok = [i for i, node in enumerate(tree) if node[3] >= 0
                  and any(m[3] == i for m in tree)]
            if not ok:
                continue
            victim = ok[int(rs.integers(len(ok)))]
        fault_kind[t], fault_victim[t] = kind, victim

    # the shapes' own order over the pool, and each trace's id: a frame
    # holds the same trees in the same places under the same ids on every
    # seed (the program packs a call's traces in the order of their ids)
    order = rs.permutation(total)
    trace_lo = rs.integers(1, 2**63, size=total)

    # ---- values: the seed draws every number
    frames = []
    for f in range(n_frames):
        strings: list[str] = []
        intern: dict[str, int] = {}

        def sid(s: str) -> int:
            i = intern.get(s)
            if i is None:
                i = intern[s] = len(strings)
                strings.append(s)
            return i

        cols: dict[str, list] = {k: [] for k in (
            "trace", "trace_lo", "span_id", "parent", "service", "name",
            "kind", "status", "start", "end")}
        attrs: list[dict] = []
        next_id = 1
        clock = START_UNIX_NANO
        for k, t in enumerate(order[f * per_frame:(f + 1) * per_frame]):
            tree = trees[ROOT_SERVICES[roots[t]]]
            clock += int(rv.integers(50_000, 2_000_000))
            start, end, status = _emit_trace(rv, tree, clock, error_rate)
            keep = np.ones(len(tree), bool)
            if fault_kind[t] >= 0:
                keep = _apply_fault(rv, FAULT_KINDS[fault_kind[t]],
                                    int(fault_victim[t]), tree, start, end,
                                    status)
            lo = int(trace_lo[t])
            ids = np.arange(next_id, next_id + len(tree))
            next_id += len(tree)
            for i, (svc, op, kind, parent, callee) in enumerate(tree):
                if not keep[i]:
                    continue
                cols["trace"].append(k)
                cols["trace_lo"].append(lo)
                cols["span_id"].append(int(ids[i]))
                cols["parent"].append(int(ids[parent]) if parent >= 0 else 0)
                cols["service"].append(sid(svc))
                cols["name"].append(sid(op))
                cols["kind"].append(kind)
                cols["status"].append(int(status[i]))
                cols["start"].append(int(start[i]))
                cols["end"].append(int(end[i]))
                if callee is not None:
                    attrs.append({"peer.service": callee})
                elif " " in op:
                    attrs.append({"http.method": op.split(" ")[0]})
                else:
                    attrs.append({})
        dtypes = {"trace": np.int64, "trace_lo": np.uint64,
                  "span_id": np.uint64, "parent": np.uint64,
                  "service": np.int32, "name": np.int32, "kind": np.int8,
                  "status": np.int8, "start": np.uint64, "end": np.uint64}
        frames.append(PlainFrame(
            strings=strings, attrs=attrs,
            **{k: np.asarray(v, dtype=dtypes[k]) for k, v in cols.items()}))
    return frames


# ------------------------------------------------------------ what is sent


def to_request(frame: PlainFrame):
    """The program's client-side request type for one pool frame, built
    once; ``rekey`` stamps the per-send ids on a copy of its id columns."""
    from odigos_tpu.pdata.spans import SpanBatchBuilder

    b = SpanBatchBuilder()
    res = {}
    for i in range(len(frame)):
        svc = frame.strings[frame.service[i]]
        if svc not in res:
            res[svc] = b.add_resource({
                "service.name": svc, "k8s.namespace.name": "default",
                "k8s.deployment.name": svc})
        b.add_span(
            trace_id=int(frame.trace_lo[i]), span_id=int(frame.span_id[i]),
            parent_span_id=int(frame.parent[i]),
            name=frame.strings[frame.name[i]], service=svc,
            kind=int(frame.kind[i]), status_code=int(frame.status[i]),
            start_unix_nano=int(frame.start[i]),
            end_unix_nano=int(frame.end[i]), resource_index=res[svc],
            attrs=frame.attrs[i] or None)
    return b.build()


def rekey(template, serial: int):
    """A copy of ``template`` whose ids are this send's own: the trace
    id's high half is the frame's serial number, span ids are shifted by
    ``serial * SERIAL_STRIDE`` (parents with them, roots stay 0)."""
    cols = dict(template.columns)
    off = np.uint64(serial * SERIAL_STRIDE)
    cols["trace_id_hi"] = np.full(len(template), serial, np.uint64)
    cols["span_id"] = template.columns["span_id"] + off
    parent = template.columns["parent_span_id"]
    cols["parent_span_id"] = np.where(parent > 0, parent + off,
                                      np.uint64(0)).astype(np.uint64)
    return type(template)(strings=template.strings,
                          resources=template.resources,
                          span_attrs=template.span_attrs, columns=cols)
