"""Batched device cycle screening for dependency graphs.

The device half of the Elle-equivalent (checker/elle/graph.py): Adya
anomaly detection is cycle detection over per-transaction dependency
graphs, and a test's history shards into many *independent* per-key
graphs (parallel/independent.py), each small.  That shape is a poor fit
for irregular host Tarjan at scale but a great fit for the MXU: pack
each graph as a (V, V) boolean adjacency matrix, batch over keys, and
compute transitive closure by repeated bfloat16 matrix squaring —
log2(V) batched matmuls.  A graph has a cycle iff its closure has a
nonzero diagonal.

The screen is conservative in the cheap direction: it decides *whether*
each key's graph is acyclic (the common, expensive-to-confirm case) on
device; only flagged keys go to the exact host search
(graph.check_cycles) for cycle extraction and Adya classification, so
verdict parity with the host path is structural.  Keys shard across the
mesh axis like the batched WGL kernel (ops/wgl_batched.py).

Equivalent role in the reference stack: elle's cycle search consumed by
jepsen at tests/cycle/{append,wr}.clj (the elle library itself is not
vendored; SURVEY.md §2.4).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np

from ..checker.elle.graph import DepGraph, check_cycles
from .. import telemetry
from ..telemetry import roofline

_kernel_cache: dict[tuple, Any] = {}


def _bucket(x: int, lo: int) -> int:
    w = lo
    while w < x:
        w *= 2
    return w


def pack_adjacency(
    graphs: Sequence[DepGraph],
    *,
    pad_keys_to: Optional[int] = None,
) -> tuple[np.ndarray, list[list[int]]]:
    """Packs graphs into a (K, V, V) bool adjacency tensor (all edge
    types collapsed — the screen only needs reachability) plus each
    graph's dense-index -> vertex mapping."""
    V = _bucket(max((len(g.vertices) for g in graphs), default=1), 8)
    K = pad_keys_to or len(graphs)
    adj = np.zeros((K, V, V), dtype=bool)
    vertex_maps: list[list[int]] = []
    for k, g in enumerate(graphs):
        verts = sorted(g.vertices)
        idx = {v: i for i, v in enumerate(verts)}
        vertex_maps.append(verts)
        for src, dsts in g.adj.items():
            si = idx[src]
            for dst in dsts:
                adj[k, si, idx[dst]] = True
    return adj, vertex_maps


def _get_kernel(K: int, V: int, mesh=None):
    # Keyed on the mesh object itself (a strong reference): id()
    # keys can collide when a dead object's address is reused,
    # silently serving a kernel compiled for something else.
    key = (K, V, mesh)
    fn = _kernel_cache.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp

    steps = max(1, int(np.ceil(np.log2(max(V, 2)))))

    def has_cycle(adj):
        # (K, V, V) bool -> (K,) bool.  Repeated squaring in bfloat16:
        # values are clamped to {0, 1} every step, so low precision
        # only ever rounds sums of nonnegative reachability counts,
        # which cannot reach zero — exactness is preserved.
        a = adj.astype(jnp.bfloat16)
        for _ in range(steps):
            a = jnp.minimum(a + jnp.einsum(
                "kij,kjh->kih", a, a,
                preferred_element_type=jnp.bfloat16,
            ), 1.0)
        diag = jnp.diagonal(a, axis1=1, axis2=2)
        return (diag > 0).any(axis=1)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        fn = roofline.instrument(jax.jit(
            jax.shard_map(
                has_cycle, mesh=mesh,
                in_specs=P("keys"), out_specs=P("keys"),
                check_vma=False,
            )
        ))
    else:
        fn = roofline.instrument(jax.jit(has_cycle))
    _kernel_cache[key] = fn
    return fn


def screen_cycles(
    graphs: Sequence[DepGraph], *, mesh=None
) -> np.ndarray:
    """(n_graphs,) bool: True where the graph contains a cycle.  Runs on
    the default JAX device, keys sharded over `mesh` when given."""
    import jax.numpy as jnp

    if not graphs:
        return np.zeros(0, dtype=bool)
    n = len(graphs)
    K = n
    if mesh is not None:
        shards = mesh.devices.size
        K = ((n + shards - 1) // shards) * shards
    adj, _ = pack_adjacency(graphs, pad_keys_to=K)
    flags = np.asarray(_get_kernel(K, adj.shape[1], mesh)(jnp.asarray(adj)))
    telemetry.count("wgl.scc.screened-graphs", n)
    return flags[:n]


# ---------------------------------------------------------------------------
# Device witness-cycle extraction (VERDICT r2 #8)
# ---------------------------------------------------------------------------


def _get_extract_kernel(K: int, V: int):
    """fn(adj_all (K,V,V) bool, adj_req (K,V,V) bool) ->
    (found (K,), u (K,), v (K,), parent (K,V), scc_size (K,)).

    Finds, per graph, one edge u->v from adj_req that lies on a cycle
    of adj_all (v reaches u), plus parent pointers of a shortest
    v->..->u path — the same parent-pointer reconstruction idea as the
    WGL witness (ops/wgl_witness.py), so only the O(len) backtrack
    happens on host.  adj_req == adj_all asks for any cycle; a
    restricted adj_req (e.g. wr-only edges) asks for a cycle THROUGH
    that edge type, which is exactly the elle layered-search primitive
    (graph.find_cycle_with_edge)."""
    key = ("extract", K, V)
    fn = _kernel_cache.get(key)
    if fn is not None:
        return fn

    import jax
    import jax.numpy as jnp
    from jax import lax

    steps = max(1, int(np.ceil(np.log2(max(V, 2)))))

    def one(adj_all, adj_req):
        a = adj_all.astype(jnp.bfloat16)
        for _ in range(steps):
            a = jnp.minimum(a + a @ a, 1.0)
        reach = a > 0                      # path of length >= 1
        eye = jnp.eye(V, dtype=bool)
        # M[u, v]: required edge u->v whose head v walks back to u
        # (trivially when u == v: a self-loop).
        m = adj_req & (reach | eye).T
        found = m.any()
        flat = jnp.argmax(m.reshape(-1))
        u = flat // V
        v = flat % V
        # SCC size of u (for scc-size reporting): mutually reachable.
        scc = reach[u] & reach[:, u]
        scc_size = jnp.maximum(scc.sum(), 1)

        # Parent BFS v -> u over adj_all.
        src_row = jnp.arange(V) == v
        init_frontier = jnp.where(found, src_row, jnp.zeros(V, bool))

        def cond(s):
            frontier, visited, parent = s
            return frontier.any() & ~visited[u]

        def body(s):
            frontier, visited, parent = s
            nxt = (
                (frontier.astype(jnp.bfloat16) @ adj_all.astype(
                    jnp.bfloat16)) > 0
            ) & ~visited
            # pred[j]: first frontier vertex with an edge to j.
            pred = jnp.argmax(frontier[:, None] & adj_all, axis=0)
            parent = jnp.where(nxt, pred, parent)
            return nxt, visited | nxt, parent

        frontier0 = init_frontier
        visited0 = init_frontier
        parent0 = jnp.where(init_frontier, v, -1).astype(jnp.int32)
        # u == v (self-loop): the trivial path needs no BFS at all.
        _, _, parent = lax.while_loop(
            cond, body,
            (frontier0 & (u != v), visited0, parent0),
        )
        return found, u.astype(jnp.int32), v.astype(jnp.int32), \
            parent, scc_size.astype(jnp.int32)

    fn = roofline.instrument(jax.jit(jax.vmap(one)))
    _kernel_cache[key] = fn
    return fn


def extract_cycles_device(
    graphs: Sequence[DepGraph],
    *,
    require: Optional[Sequence[Optional[set]]] = None,
) -> list[Optional[tuple[list[int], int]]]:
    """Per graph: (cycle as a closed vertex list [v0..v0], scc_size),
    or None when no qualifying cycle exists.  `require[i]` restricts
    graph i's cycle to pass through at least one edge carrying one of
    those types (the elle layer rule); None means any cycle.

    The O(V^3) closure + BFS sweep runs on device; the host only
    backtracks parent pointers."""
    import jax.numpy as jnp

    if not graphs:
        return []
    adj_all, vertex_maps = pack_adjacency(graphs)
    K, V, _ = adj_all.shape
    adj_req = adj_all.copy()
    if require is not None:
        for k, (g, types) in enumerate(zip(graphs, require)):
            if types is None:
                continue
            verts = vertex_maps[k]
            idx = {x: i for i, x in enumerate(verts)}
            req = np.zeros((V, V), dtype=bool)
            for src, dsts in g.adj.items():
                for dst, ts in dsts.items():
                    if ts & set(types):
                        req[idx[src], idx[dst]] = True
            adj_req[k] = req
    found, u, v, parent, scc = (
        np.asarray(x) for x in _get_extract_kernel(K, V)(
            jnp.asarray(adj_all), jnp.asarray(adj_req)
        )
    )
    out: list[Optional[tuple[list[int], int]]] = []
    for k in range(K):
        if not found[k]:
            out.append(None)
            continue
        verts = vertex_maps[k]
        uu, vv = int(u[k]), int(v[k])
        # Path vv -> .. -> uu via parents, then the uu -> vv edge
        # closes it.  Format matches graph.find_cycle_in: closed list.
        path = [uu]
        guard = 0
        while path[-1] != vv and guard <= V:
            path.append(int(parent[k][path[-1]]))
            guard += 1
        if guard > V:  # unreachable (shouldn't happen): be safe
            out.append(None)
            continue
        path.reverse()                    # vv .. uu
        cycle_idx = [vv] if uu == vv else path
        cycle = [verts[i] for i in cycle_idx] + [verts[vv]]
        out.append((cycle, int(scc[k])))
    return out


def _record(g: DepGraph, cycle: list[int], scc_size: int,
            forced: Optional[str]) -> dict:
    from ..checker.elle.graph import classify_cycle, cycle_explanation

    return {
        "type": forced or classify_cycle(g, cycle),
        "cycle": cycle,
        "steps": cycle_explanation(g, cycle),
        "scc-size": scc_size,
    }


#: sentinel forced-type for the leftovers layer (classification is
#: derived from the cycle itself, like graph.check_cycles layer 4)
_LAYER4 = "__leftover__"


def check_cycles_layered_device_batch(
    graphs: Sequence[DepGraph],
) -> list[list[dict]]:
    """graph.check_cycles' layer structure with the cycle search on
    device, batched over graphs: G0 over the ww subgraph, G1c through
    a wr edge over ww+wr, G-single/G2-item through an rw edge over
    everything, and a leftovers layer (any cycle at all — custom or
    realtime/process-only edge types must not pass as valid, exactly
    like the host's layer 4).  Every layer of every graph rides ONE
    extract_cycles_device call.

    One witness record per non-empty layer per graph — the host path
    emits one per SCC per layer; this path exists for graphs whose
    host Tarjan is the bottleneck, where one certificate per anomaly
    class is what the checker consumes (checker/elle reports types +
    examples), at the cost of possibly under-reporting extra SCCs."""
    entries: list[tuple[int, DepGraph, Optional[set], Optional[str]]] = []
    for gi, graph in enumerate(graphs):
        layers = [
            (graph.restricted(["ww", "realtime", "process"]),
             None, "G0"),
            (graph.restricted(["ww", "wr", "realtime", "process"]),
             {"wr"}, "G1c"),
            (graph, {"rw"}, None),
            (graph, None, _LAYER4),
        ]
        for g, req, t in layers:
            if g.vertices:
                entries.append((gi, g, req, t))
    results = extract_cycles_device(
        [e[1] for e in entries], require=[e[2] for e in entries],
    )
    out: list[list[dict]] = [[] for _ in graphs]
    leftovers: list[tuple[int, DepGraph, tuple]] = []
    for (gi, g, _req, forced), res in zip(entries, results):
        if res is None:
            continue
        if forced == _LAYER4:
            leftovers.append((gi, g, res))
            continue
        cycle, scc_size = res
        out[gi].append(_record(g, cycle, scc_size, forced))
    for gi, g, (cycle, scc_size) in leftovers:
        # Report only what the typed layers left unexplained: a cycle
        # sharing vertices with an already-reported one is the same
        # SCC seen again through a looser lens.
        seen = [set(r["cycle"]) for r in out[gi]]
        if any(set(cycle) & s for s in seen):
            continue
        out[gi].append(_record(g, cycle, scc_size, None))
    return out


def check_cycles_layered_device(graph: DepGraph) -> list[dict]:
    return check_cycles_layered_device_batch([graph])[0]


def check_cycles_device(
    graphs: Sequence[DepGraph], *, mesh=None,
    max_device_vertices: int = 1024,
    device_extract_min_vertices: int = 256,
) -> list[list[dict]]:
    """Anomaly cycles per graph, device-screened: acyclic keys are
    settled by the closure kernel; small flagged keys get the exact
    host layered search (same records as graph.check_cycles); LARGE
    flagged keys extract their witness cycles on device too
    (check_cycles_layered_device), so a huge cyclic key no longer
    serializes on host Tarjan.  Graphs too large for a dense (V, V)
    matrix fall back to host entirely."""
    big = [
        i for i, g in enumerate(graphs)
        if len(g.vertices) > max_device_vertices
    ]
    small_idx = [i for i in range(len(graphs)) if i not in set(big)]
    small = [graphs[i] for i in small_idx]
    out: list[list[dict]] = [[] for _ in graphs]
    device_bound: list[int] = []
    if small:
        flags = screen_cycles(small, mesh=mesh)
        for i, flagged in zip(small_idx, flags):
            if not flagged:
                continue
            if len(graphs[i].vertices) >= device_extract_min_vertices:
                device_bound.append(i)
            else:
                out[i] = check_cycles(graphs[i])
    if device_bound:
        # One batched extraction for every large flagged key — not a
        # serial per-key device round-trip.
        recs = check_cycles_layered_device_batch(
            [graphs[i] for i in device_bound]
        )
        for i, r in zip(device_bound, recs):
            out[i] = r
    for i in big:
        out[i] = check_cycles(graphs[i])
    return out
