"""Cycle detection over dependency graphs: the Elle core, device-first.

The reference's Elle searches dependency graphs of up to ~100k txns for
cycles (SURVEY.md §2.4). The device kernel here is *iterative trimming*
(Karp-style 2-core peeling): repeatedly drop nodes with no active in-edge
or no active out-edge, entirely with ``segment_sum`` over edge lists under
``lax.while_loop``. After convergence:

* residue empty  => the graph is acyclic (serializable: no anomaly).
* otherwise the residue — every cycle lives inside it, but long-diameter
  graphs may leave acyclic chains when the peel hits its iteration cap —
  is handed to an exact host-side Tarjan for SCC extraction and cycle
  classification. The residue is always a *superset* of the cycle nodes;
  only the exact pass's verdict counts.

The trim is O(E) per iteration with ~diameter iterations, fully
data-parallel, and edge arrays shard cleanly over a device mesh (segment
sums become psum-reduced partials). Running it per edge-type-filtered
subgraph (ww-only, ww+wr) answers G0/G1c directly.
"""
from __future__ import annotations

from functools import partial

import numpy as np


_TRIM_CACHE: dict = {}


def _trim_kernel(n_nodes: int, n_edges: int, max_iters: int):
    """Compiled trim kernel for bucketed (n_nodes, n_edges) shapes. Edge
    arrays are runtime arguments (with a validity mask for padding), NOT
    trace-time constants — so one compilation serves every graph in the
    same shape bucket instead of re-jitting per call."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    key = (n_nodes, n_edges, max_iters)
    fn = _TRIM_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def run(src_j, dst_j, valid):
        def body(carry):
            active, _, it = carry
            edge_active = valid & active[src_j] & active[dst_j]
            indeg = jax.ops.segment_sum(edge_active.astype(jnp.int32), dst_j,
                                        num_segments=n_nodes)
            outdeg = jax.ops.segment_sum(edge_active.astype(jnp.int32), src_j,
                                         num_segments=n_nodes)
            new_active = active & (indeg > 0) & (outdeg > 0)
            changed = jnp.any(new_active != active)
            return new_active, changed, it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iters)

        active0 = jnp.ones((n_nodes,), dtype=bool)
        active, _, _ = lax.while_loop(cond, body, (active0, jnp.bool_(True),
                                                   jnp.int32(0)))
        return active

    _TRIM_CACHE[key] = run
    return run


def trim_to_cycles(n_nodes: int, src: np.ndarray, dst: np.ndarray,
                   max_iters: int = 512):
    """Device trim: returns a bool[n_nodes] mask of nodes surviving 2-core
    peeling (empty => acyclic; every cycle is inside the residue). Peeling
    removes one fringe layer per iteration, so a near-serial history (a
    ~n-long dependency chain) would need ~n iterations to fully converge;
    the cap keeps device time bounded and leaves a conservative residue
    that the exact host pass classifies.

    Node and edge counts are bucketed to powers of two (padding nodes have
    no edges and peel away in the first iteration; padding edges carry a
    False validity bit), so nearby graph sizes share one compilation."""
    from jepsen_tpu.ops.jitlin import _bucket

    if len(src) == 0 or n_nodes == 0:
        return np.zeros(n_nodes, dtype=bool)

    nb = _bucket(n_nodes, floor=64)
    eb = _bucket(len(src), floor=64)
    pad = eb - len(src)
    src_p = np.concatenate([np.asarray(src, np.int32),
                            np.zeros(pad, np.int32)])
    dst_p = np.concatenate([np.asarray(dst, np.int32),
                            np.zeros(pad, np.int32)])
    valid = np.concatenate([np.ones(len(src), bool), np.zeros(pad, bool)])
    run = _trim_kernel(nb, eb, max_iters)
    return np.asarray(run(src_p, dst_p, valid))[:n_nodes]


def has_cycle(n_nodes: int, src, dst) -> bool:
    """Exact cycle test: device trim narrows, host Tarjan confirms (a
    capped trim's residue may contain acyclic chains)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    mask = trim_to_cycles(n_nodes, src, dst)
    if not mask.any():
        return False
    kept = set(np.nonzero(mask)[0].tolist())
    edges = [(int(s), int(d)) for s, d in zip(src, dst)
             if s in kept and d in kept]
    return bool(tarjan_scc(n_nodes, edges))


def trim_to_cycles_sharded(n_nodes: int, src: np.ndarray, dst: np.ndarray,
                           mesh, max_iters: int = 512):
    """Edge-sharded device trim: the same capped 2-core peeling as
    :func:`trim_to_cycles` (same loose-superset residue contract — the
    exact host pass is authoritative), but with the edge list sharded over
    the mesh's first axis under ``shard_map``. Each device computes partial in/out
    degrees for its edge shard with ``segment_sum``; partials are reduced
    with ``psum`` (ICI all-reduce on a pod), so the node-activity vector is
    replicated while edge traffic stays device-local. This is the 50k-txn
    Elle-graph scaling path (BASELINE config 5, SURVEY.md §5.8)."""
    import jax

    if len(src) == 0 or n_nodes == 0:
        return np.zeros(n_nodes, dtype=bool)

    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = mesh.devices.size
    E = len(src)
    pad = (-E) % n_dev
    # Padding edges carry weight 0 so they contribute no degree.
    src_p = np.concatenate([np.asarray(src, np.int32), np.zeros(pad, np.int32)])
    dst_p = np.concatenate([np.asarray(dst, np.int32), np.zeros(pad, np.int32)])
    w_p = np.concatenate([np.ones(E, np.int32), np.zeros(pad, np.int32)])

    esh = NamedSharding(mesh, P(mesh.axis_names[0]))
    sj = jax.device_put(src_p, esh)
    dj = jax.device_put(dst_p, esh)
    wj = jax.device_put(w_p, esh)
    return np.asarray(run_sharded_trim(mesh, n_nodes, sj, dj, wj, max_iters))


def run_sharded_trim(mesh, n_nodes: int, sj, dj, wj, max_iters: int = 512):
    """The compute half of the sharded trim, over ALREADY-PLACED edge
    arrays (sharded on the mesh's first axis with weight 0 padding).
    Split out so the multi-process (DCN) path can place per-process
    local shards with make_array_from_process_local_data and run the
    identical kernel (jepsen_tpu.parallel.distributed)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def degrees(active, s, d, w):
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P(axis), P(axis), P(axis)), out_specs=P())
        def go(active, s, d, w):
            ew = w * (active[s] & active[d]).astype(jnp.int32)
            indeg = jax.ops.segment_sum(ew, d, num_segments=n_nodes)
            outdeg = jax.ops.segment_sum(ew, s, num_segments=n_nodes)
            return lax.psum(jnp.stack([indeg, outdeg]), axis)

        return go(active, s, d, w)

    @jax.jit
    def run(s, d, w):
        def body(carry):
            active, _, it = carry
            deg = degrees(active, s, d, w)
            new_active = active & (deg[0] > 0) & (deg[1] > 0)
            changed = jnp.any(new_active != active)
            return new_active, changed, it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_iters)

        active0 = jnp.ones((n_nodes,), dtype=bool)
        active, _, _ = lax.while_loop(
            cond, body, (active0, jnp.bool_(True), jnp.int32(0)))
        return active

    return run(sj, dj, wj)


_SCREEN_CACHE: dict = {}


def _screen_kernel(n_clusters: int, n_local: int, n_edges: int):
    """Compiled batched-closure screen for bucketed (B, V, E) shapes.

    One boolean adjacency matrix per cluster, [B, V, V]; transitive
    closure by repeated squaring — ``ceil(log2(V))`` batched bf16
    matmuls on the MXU (R := R ∨ R·R doubles the covered path length
    each step, so it has fully converged once 2^steps >= V; the result
    is EXACT, unlike the capped peeling trim). A cluster contains a
    cycle iff its closure has a nonzero diagonal.

    bf16 operands with float32 accumulation (`preferred_element_type`)
    keep the MXU path while making the >0 threshold exact: entries are
    0/1, so any true sum is >= 1 and cannot round to 0."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    key = (n_clusters, n_local, n_edges)
    fn = _SCREEN_CACHE.get(key)
    if fn is not None:
        return fn

    n_steps = screen_steps(n_local)

    # its own name, so the profiler tells its XLA program
    # (``jit_cluster_screen``) from the trim's and the frontier scan's
    @jax.jit
    def cluster_screen(cid, src_l, dst_l, valid):
        adj = jnp.zeros((n_clusters, n_local, n_local), jnp.bfloat16)
        adj = adj.at[cid, src_l, dst_l].max(
            jnp.where(valid, jnp.bfloat16(1), jnp.bfloat16(0)))

        def body(_, r):
            sq = jax.lax.dot_general(
                r, r,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return jnp.maximum(r, (sq > 0).astype(jnp.bfloat16))

        closure = lax.fori_loop(0, n_steps, body, adj)
        diag = jnp.diagonal(closure, axis1=1, axis2=2)
        return jnp.any(diag > 0, axis=1)

    _SCREEN_CACHE[key] = cluster_screen
    return cluster_screen


def screen_steps(n_local: int) -> int:
    """Squaring steps of the closure over ``n_local`` nodes:
    ``ceil(log2(V))``, at least one."""
    return max(1, int(np.ceil(np.log2(max(2, n_local)))))


# ceiling on one screen dispatch's [B, V, V] element count: bf16
# adjacency ~64 MB and the f32 dot_general intermediate ~128 MB at this
# size — batches beyond it are chunked along the cluster axis
SCREEN_MAX_ELEMS = 1 << 25


def batch_cluster_screen(cid: np.ndarray, src_l: np.ndarray,
                         dst_l: np.ndarray, n_clusters: int,
                         max_local: int) -> np.ndarray:
    """Exact per-cluster cycle screen on device: returns bool[n_clusters],
    True iff cluster ``c`` (edges where ``cid == c``, node ids already
    LOCAL to the cluster) contains a directed cycle.

    This is the device half of the φ-interval Elle path (see
    jepsen_tpu.elle.check_cycles): the host localizes all possible cycle
    nodes into small clusters, and this kernel settles every cluster's
    has-a-cycle question in ONE dispatch — batched [B, V, V] boolean
    matrix squaring instead of the reference's per-graph host Tarjan
    (jepsen/src/jepsen/tests/cycle.clj's SCC search). Transfers are edge
    lists (KBs), not matrices; shapes are bucketed so compilations cache."""
    from jepsen_tpu.ops.jitlin import _bucket

    if n_clusters == 0:
        return np.zeros(0, dtype=bool)
    if len(cid) == 0:
        return np.zeros(n_clusters, dtype=bool)

    vb = _bucket(max_local, floor=8)
    # element budget: chunk the cluster axis when B*V^2 would exceed it
    # (callers bucket clusters by size, so V is tight for every chunk)
    b_max = max(1, SCREEN_MAX_ELEMS // (vb * vb))
    if n_clusters > b_max:
        cid = np.asarray(cid, np.int64)
        out = np.zeros(n_clusters, dtype=bool)
        for b0 in range(0, n_clusters, b_max):
            b1 = min(b0 + b_max, n_clusters)
            m = (cid >= b0) & (cid < b1)
            out[b0:b1] = batch_cluster_screen(
                (cid[m] - b0).astype(np.int32), src_l[m], dst_l[m],
                b1 - b0, max_local)
        return out

    from jepsen_tpu import trace

    bb = _bucket(n_clusters, floor=8)
    eb = _bucket(len(cid), floor=64)
    pad = eb - len(cid)
    cid_p = np.concatenate([np.asarray(cid, np.int32),
                            np.zeros(pad, np.int32)])
    src_p = np.concatenate([np.asarray(src_l, np.int32),
                            np.zeros(pad, np.int32)])
    dst_p = np.concatenate([np.asarray(dst_l, np.int32),
                            np.zeros(pad, np.int32)])
    valid = np.concatenate([np.ones(len(cid), bool), np.zeros(pad, bool)])
    screen = _screen_kernel(bb, vb, eb)
    # one dispatch, readback included, with the bucketed shapes the
    # device works on
    with trace.phase("dispatch.elle_screen", b=bb, v=vb, e=eb,
                     steps=screen_steps(vb)):
        return np.asarray(screen(cid_p, src_p, dst_p, valid))[:n_clusters]


def tarjan_scc(n_nodes: int, edges: list[tuple[int, int]]) -> list[list[int]]:
    """Exact SCCs, iterative Tarjan (host-side; used on the trimmed
    residue). Returns SCCs with >1 node or a self-loop."""
    adj: list[list[int]] = [[] for _ in range(n_nodes)]
    self_loop = set()
    for s, d in edges:
        if s == d:
            self_loop.add(s)
        adj[s].append(d)
    index = [-1] * n_nodes
    low = [0] * n_nodes
    on_stack = [False] * n_nodes
    stack: list[int] = []
    sccs: list[list[int]] = []
    counter = [0]

    for root in range(n_nodes):
        if index[root] != -1:
            continue
        work = [(root, 0)]
        while work:
            v, pi = work[-1]
            if pi == 0:
                index[v] = low[v] = counter[0]
                counter[0] += 1
                stack.append(v)
                on_stack[v] = True
            recurse = False
            for i in range(pi, len(adj[v])):
                w = adj[v][i]
                if index[w] == -1:
                    work[-1] = (v, i + 1)
                    work.append((w, 0))
                    recurse = True
                    break
                elif on_stack[w]:
                    low[v] = min(low[v], index[w])
            if recurse:
                continue
            if low[v] == index[v]:
                scc = []
                while True:
                    w = stack.pop()
                    on_stack[w] = False
                    scc.append(w)
                    if w == v:
                        break
                if len(scc) > 1 or v in self_loop:
                    sccs.append(scc)
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[v])
    return sccs


def find_cycle_in_scc(scc: list[int], edges: list[tuple[int, int, str]],
                      prefer_fewest: str | None = None):
    """Finds one cycle within an SCC as [(src, dst, type), ...].
    With prefer_fewest='rw', tries to find a cycle using as few edges of
    that type as possible (distinguishes G-single from G2, mirroring
    Elle's typed cycle searches)."""
    in_scc = set(scc)
    adj: dict[int, list[tuple[int, str]]] = {v: [] for v in scc}
    for s, d, t in edges:
        if s in in_scc and d in in_scc:
            adj[s].append((d, t))

    def bfs_cycle(allowed):
        """Shortest cycle through each start using only allowed edge types,
        then one optional non-allowed edge... simple variant: BFS from each
        node back to itself."""
        for start in scc:
            # BFS over (node) with parent tracking
            prev: dict[int, tuple[int, str]] = {}
            frontier = [start]
            seen = {start}
            found = None
            while frontier and found is None:
                nxt = []
                for u in frontier:
                    for (w, t) in adj[u]:
                        if allowed is not None and t not in allowed:
                            continue
                        if w == start:
                            prev[("end",)] = (u, t)
                            found = True
                            break
                        if w not in seen:
                            seen.add(w)
                            prev[w] = (u, t)
                            nxt.append(w)
                    if found:
                        break
                frontier = nxt
            if found:
                cycle = []
                node, t = prev[("end",)]
                cycle.append((node, start, t))
                while node != start:
                    pnode, pt = prev[node]
                    cycle.append((pnode, node, pt))
                    node = pnode
                cycle.reverse()
                return cycle
        return None

    if prefer_fewest is not None:
        others = {t for _, _, t in edges if t != prefer_fewest}
        c = bfs_cycle(others)  # zero rw edges
        if c is not None:
            return c
        # allow exactly one rw: BFS where the rw edge is taken first
        for s, d, t in edges:
            if t != prefer_fewest or s not in in_scc or d not in in_scc:
                continue
            path = _bfs_path(adj, d, s, others)
            if path is not None:
                return [(s, d, t)] + path
    return bfs_cycle(None)


def _bfs_path(adj, start, goal, allowed):
    """Shortest path start->goal using allowed edge types, as
    [(src, dst, type), ...]; None if unreachable."""
    if start == goal:
        return []
    prev: dict[int, tuple[int, str]] = {}
    frontier = [start]
    seen = {start}
    while frontier:
        nxt = []
        for u in frontier:
            for (w, t) in adj.get(u, []):
                if t not in allowed or w in seen:
                    continue
                seen.add(w)
                prev[w] = (u, t)
                if w == goal:
                    path = []
                    node = w
                    while node != start:
                        p, pt = prev[node]
                        path.append((p, node, pt))
                        node = p
                    path.reverse()
                    return path
                nxt.append(w)
        frontier = nxt
    return None
