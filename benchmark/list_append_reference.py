"""A plain list-append checker: the anomalies of a list-append history,
from its op dicts alone, by plain graph search.

This is the reference the ``elle_append`` configuration's answers are
held to (``checkers/elle_append.py``). It shares no code with the
program: it imports nothing from ``jepsen_tpu``. Its definitions are
written from the anomalies' own definitions (Adya's G0 to G2, Elle's
list-append inferences) and from the program's documented semantics
(``jepsen_tpu/elle/__init__.py``'s module docstring and the list-append
checker's), and it reports what the program reports: the set of anomaly
types, by the program's names, and whether any of them is proscribed.

The history. A txn is an invocation and its completion by one process;
its value is a list of micro-ops ``["append", k, v]`` and ``["r", k,
list]``. The graph's nodes are the ok txns. A failed txn took no effect
and is no node; its appends are remembered for aborted reads.

Version order, one per key: the longest read of the key (the first such
in completion order) is its order, and a read is expected to be a
prefix of it. The writer of ``(k, v)`` is the first ok txn, in
completion order, that appends ``v`` to ``k``.

Edges, none from a txn to itself:

* ww: the writers of consecutive elements of a key's order (a pair
  whose element has no writer gives none);
* wr: the writer of a read's last element, to the reader;
* rw: the reader, to the writer of the element that follows its read in
  the key's order;
* process: each ok txn to the next ok txn of its process;
* realtime: A precedes B when A completed before B was invoked. Not as
  n^2 edges: a chain of time nodes, one per history position, each to
  the next; A links to the node of its completion, and the node of B's
  invocation links to B. A reaches B through the chain exactly when A
  completed before B was invoked.

Anomalies, with the program's names:

* ``G0``: a cycle of ww edges;
* ``G1c``: a cycle of ww and wr edges through a wr edge;
* ``G-single`` / ``G2``: per strongly connected component of the ww, wr
  and rw edges that holds no cycle of ww and wr edges alone: ``G-single``
  where a cycle through exactly one rw edge exists, else ``G2``;
* ``process-cycle``: a cycle of dependency and process edges through a
  process edge;
* ``realtime-cycle``: a cycle of dependency, process and realtime order
  through the realtime order (a time node on a cycle);
* ``G1a``: a read holds a value a failed txn appended;
* ``G1b``: a read holds a proper prefix of the values one other txn
  appended to the key;
* ``internal``: a txn's read does not end with the values the txn itself
  appended to the key before it;
* ``incompatible-order``: a read that is not a prefix of its key's
  order, or that holds another txn's appends out of their order;
* ``duplicate-elements``: a read that holds a value twice;
* ``duplicate-appends``: a value appended to a key twice.

Under strict serializability every one of these is proscribed; with
``timing=False`` (the control) the process and realtime edges are
dropped, which is serializability, and the two timing anomalies cannot
arise.

Departures: an ``info`` (indeterminate) txn, or a completion with no
invocation, is refused (ValueError): the configuration assumes none,
where the program would take an indeterminate txn's appends as
possibly real. A read of a value no txn appended is no anomaly, as in
the program (it reports it as informational only).
"""
from __future__ import annotations

from collections import defaultdict, deque

import numpy as np

WW, WR, RW, PROCESS = 0, 1, 2, 3


def _txns(history: list[dict]):
    """(ok txns as (invoke position, completion position, process,
    micro-ops), failed txns' micro-ops), in completion order."""
    open_at: dict = {}
    oks, failed = [], []
    for pos, op in enumerate(history):
        t, p = op["type"], op["process"]
        if t == "invoke":
            open_at[p] = pos
            continue
        if p not in open_at:
            raise ValueError(f"completion at {pos} has no invocation")
        inv = open_at.pop(p)
        if t == "ok":
            oks.append((inv, pos, p, op["value"]))
        elif t == "fail":
            failed.append(op["value"])
        else:
            raise ValueError(f"indeterminate txn at {pos}")
    return oks, failed


def _strong_labels(n: int, src: list, dst: list) -> np.ndarray:
    """Strongly connected component labels of n nodes, -1 for a node in
    no cycle (no self-edges are made)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components
    if not src:
        return np.full(n, -1)
    m = coo_matrix((np.ones(len(src), np.int8), (src, dst)), shape=(n, n))
    _, labels = connected_components(m.tocsr(), directed=True,
                                     connection="strong")
    sizes = np.bincount(labels)
    return np.where(sizes[labels] > 1, labels, -1)


def _reaches(adj: dict, start: int, goal: int) -> bool:
    seen = {start}
    todo = deque([start])
    while todo:
        u = todo.popleft()
        if u == goal:
            return True
        for w in adj.get(u, ()):
            if w not in seen:
                seen.add(w)
                todo.append(w)
    return False


def _read_anomalies(oks, failed, found: set):
    """The non-cyclic anomalies; returns the dependency edges as
    {type: (src list, dst list)}."""
    writer: dict = {}
    appends = defaultdict(list)        # (txn, key) -> values, in order
    for i, (_, _, _, mops) in enumerate(oks):
        for f, k, v in mops:
            if f == "append":
                if (k, v) in writer:
                    found.add("duplicate-appends")
                    continue
                writer[k, v] = i
                appends[i, k].append(v)
    aborted = {(k, v) for mops in failed for f, k, v in mops or ()
               if f == "append"}

    reads = defaultdict(list)          # key -> [(txn, read)]
    for i, (_, _, _, mops) in enumerate(oks):
        mine = defaultdict(list)
        for f, k, v in mops:
            if f == "append":
                mine[k].append(v)
            elif v is not None:
                v = list(v)
                reads[k].append((i, v))
                own = mine[k]
                if own and v[-len(own):] != own:
                    found.add("internal")

    edges = {t: ([], []) for t in (WW, WR, RW)}

    def edge(t, a, b):
        if a is not None and b is not None and a != b:
            edges[t][0].append(a)
            edges[t][1].append(b)

    for k, rs in reads.items():
        order = max((r for _, r in rs), key=len)
        writers = [writer.get((k, v)) for v in order]
        for a, b in zip(writers, writers[1:]):
            edge(WW, a, b)
        for i, r in rs:
            if r != order[:len(r)]:
                found.add("incompatible-order")
            if len(set(r)) != len(r):
                found.add("duplicate-elements")
            by_writer = defaultdict(list)
            for v in r:
                if (k, v) in aborted:
                    found.add("G1a")
                elif (k, v) in writer:
                    by_writer[writer[k, v]].append(v)
            for w, seen in by_writer.items():
                if w == i or seen == appends[w, k]:
                    continue
                found.add("G1b" if seen == appends[w, k][:len(seen)]
                          else "incompatible-order")
            if r:
                edge(WR, writer.get((k, r[-1])), i)
            if len(r) < len(order):
                edge(RW, i, writer.get((k, order[len(r)])))
    return edges


def _timing_edges(oks, n_positions: int):
    """(process edges, realtime links) over txn nodes 0..n-1 and time
    nodes n..n+n_positions-1."""
    n = len(oks)
    last: dict = {}
    proc = ([], [])
    for i, (_, _, p, _) in enumerate(oks):
        if p in last:
            proc[0].append(last[p])
            proc[1].append(i)
        last[p] = i
    chain = np.arange(n, n + n_positions - 1)
    src = [chain, np.arange(n)]
    dst = [chain + 1, n + np.asarray([c for _, c, _, _ in oks], np.int64)]
    src.append(n + np.asarray([v for v, _, _, _ in oks], np.int64))
    dst.append(np.arange(n))
    return proc, (np.concatenate(src).tolist(), np.concatenate(dst).tolist())


def anomalies(history: list[dict], timing: bool = True) -> set:
    """The anomaly types of ``history``: under strict serializability's
    graph (``timing``), or with the process and realtime order dropped."""
    oks, failed = _txns(history)
    n = len(oks)
    found: set = set()
    dep = _read_anomalies(oks, failed, found)

    src = dep[WW][0] + dep[WR][0] + dep[RW][0]
    dst = dep[WW][1] + dep[WR][1] + dep[RW][1]
    proc = ([], [])
    total = n
    if timing:
        proc, (t_src, t_dst) = _timing_edges(oks, len(history))
        total = n + len(history)
        labels = _strong_labels(total, src + proc[0] + t_src,
                                dst + proc[1] + t_dst)
        if (labels[n:] >= 0).any():
            found.add("realtime-cycle")
    else:
        labels = _strong_labels(n, src, dst)
    core = labels[:n] >= 0
    if not core.any():
        return found

    # every cycle of a part of the graph lies inside one strongly
    # connected component of the whole: search the core alone
    def typed(*types):
        es = [(a, b, t) for t in types
              for a, b in zip(*(proc if t == PROCESS else dep[t]))
              if core[a] and core[b]]
        return es

    def cyclic(es) -> np.ndarray:
        return _strong_labels(n, [a for a, _, _ in es], [b for _, b, _ in es])

    ww = cyclic(typed(WW))
    if (ww >= 0).any():
        found.add("G0")
    g1 = typed(WW, WR)
    g1_labels = cyclic(g1)
    if any(t == WR and g1_labels[a] >= 0 and g1_labels[a] == g1_labels[b]
           for a, b, t in g1):
        found.add("G1c")
    if timing:
        dp = typed(WW, WR, RW, PROCESS)
        dp_labels = cyclic(dp)
        if any(t == PROCESS and dp_labels[a] >= 0
               and dp_labels[a] == dp_labels[b] for a, b, t in dp):
            found.add("process-cycle")

    d = typed(WW, WR, RW)
    d_labels = cyclic(d)
    for c in set(d_labels[d_labels >= 0].tolist()):
        inside = [(a, b, t) for a, b, t in d
                  if d_labels[a] == c and d_labels[b] == c]
        no_rw = [(a, b) for a, b, t in inside if t != RW]
        if no_rw and (_strong_labels(
                n, [a for a, _ in no_rw], [b for _, b in no_rw]) >= 0).any():
            continue        # a cycle without rw: G0 or G1c, not G-single
        adj = defaultdict(list)
        for a, b in no_rw:
            adj[a].append(b)
        if any(_reaches(adj, b, a) for a, b, t in inside if t == RW):
            found.add("G-single")
        else:
            found.add("G2")
    return found


def check(history: list[dict], timing: bool = True) -> tuple[bool, tuple]:
    """(valid, sorted anomaly types): valid where no anomaly was found,
    every one being proscribed."""
    found = anomalies(history, timing)
    return not found, tuple(sorted(found))
