"""On-the-fly fixed-fanout mini-batch sampling -> padded MFG blocks.

GraphStorm/DistDGL samples variable-degree neighborhoods into dynamic CSR
minibatches on CPU workers.  JAX/TPU wants static shapes, so the TPU-native
re-think is *tree-structured fixed-fanout sampling*: every dst node draws
exactly ``fanout`` in-neighbors per edge type (sampling with replacement
when deg > 0; masked rows when deg == 0).  A frontier at layer l-1 is the
concatenation, in deterministic order, of

    [dst nodes themselves (self rows)] ++ [per-etype sampled neighbors]

so each MFG block only needs offsets + masks — neighbor *positions* are
implicit, and the aggregation becomes a dense (num_dst, fanout, dim)
masked mean: exactly the seg_aggr Pallas kernel's layout.

Sampling stays on the host (numpy), mirroring DistDGL's CPU samplers.
What crosses into jit depends on the feed mode (docs/pipeline.md): the
host path ships gathered feature blocks (``fetch_features``), the
device-resident path ships only the int32 frontier index arrays and bool
masks — raw features live on device in a
``repro.core.feature_store.DeviceFeatureStore`` and are gathered in-jit.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.graph import EType, HeteroGraph


@dataclasses.dataclass
class EdgeBlockInfo:
    etype: EType
    num_dst: int
    fanout: int
    src_offset: int           # row offset of sampled nbrs in src-ntype frontier
    mask: np.ndarray          # (num_dst, fanout) bool
    nbr_global: np.ndarray    # (num_dst, fanout) global src ids (for debug/excl)
    edge_ids: np.ndarray      # (num_dst, fanout) sampled edge ids
    delta_t: Optional[np.ndarray] = None  # (num_dst, fanout) temporal graphs


@dataclasses.dataclass
class MFGBlock:
    """One message-flow layer: frontier[l-1] (inputs) -> frontier[l] (outputs)."""
    dst_counts: Dict[str, int]              # per dst ntype
    src_counts: Dict[str, int]              # per src ntype (frontier rows)
    self_offsets: Dict[str, int]            # where dst rows sit in src frontier
    edge_blocks: List[EdgeBlockInfo]
    src_nodes: Dict[str, np.ndarray]        # frontier[l-1] global ids per ntype
    dst_nodes: Dict[str, np.ndarray]        # frontier[l]   global ids per ntype


@dataclasses.dataclass
class MiniBatch:
    blocks: List[MFGBlock]                  # length = num GNN layers
    input_nodes: Dict[str, np.ndarray]      # frontier[0] ids per ntype
    seeds: Dict[str, np.ndarray]            # seed ids per ntype
    seed_mask: Dict[str, np.ndarray]        # padding mask per ntype


class NeighborSampler:
    """Fixed-fanout sampler over a HeteroGraph.

    fanouts: one int per GNN layer (applied to every edge type), or a list
    of dicts {etype: fanout}.
    """

    def __init__(self, graph: HeteroGraph, fanouts: Sequence,
                 seed: int = 0,
                 exclude_edges: Optional[Dict[EType, set]] = None,
                 restrict_nodes: Optional[Dict[str, np.ndarray]] = None):
        self.g = graph
        self.fanouts = list(fanouts)
        self.rng = np.random.default_rng(seed)
        self.exclude_edges = exclude_edges or {}
        self.restrict = restrict_nodes

    # ------------------------------------------------------------------
    def _sample_neighbors(self, etype: EType, dst_ids: np.ndarray,
                          fanout: int,
                          exclude_pairs: Optional[set] = None):
        """Returns (nbrs (n,f), eids (n,f), mask (n,f))."""
        csc = self.g.csc(etype)
        n = len(dst_ids)
        nbrs = np.zeros((n, fanout), np.int64)
        eids = np.zeros((n, fanout), np.int64)
        mask = np.zeros((n, fanout), bool)
        starts = csc.indptr[dst_ids]
        degs = csc.indptr[dst_ids + 1] - starts
        has = degs > 0
        if not has.any():
            return nbrs, eids, mask
        # vectorized with-replacement draw for all rows at once
        draw = self.rng.integers(0, np.maximum(degs, 1)[:, None],
                                 size=(n, fanout))
        flat = starts[:, None] + draw
        # rows with deg==0 may point one past the last edge; clamp (they
        # are masked out below anyway)
        flat = np.minimum(flat, len(csc.indices) - 1)
        nbrs = csc.indices[flat]
        eids = csc.edge_ids[flat]
        mask = np.broadcast_to(has[:, None], (n, fanout)).copy()
        # degree < fanout: keep only ceil draws? with replacement we keep all;
        # rows with deg==0 are fully masked and point at node 0 (padded)
        nbrs[~mask] = 0
        if exclude_pairs:
            # SpotTarget: mask out sampled edges that are batch targets.
            # encode (src, dst) pairs as a single int for vectorized isin
            n_src = self.g.num_nodes[etype[0]]
            codes = nbrs * np.int64(self.g.num_nodes[etype[2]]) \
                + dst_ids[:, None]
            excl = np.fromiter(
                (int(s) * self.g.num_nodes[etype[2]] + int(d)
                 for s, d in exclude_pairs), np.int64, len(exclude_pairs))
            mask &= ~np.isin(codes, excl)
        return nbrs, eids, mask

    # ------------------------------------------------------------------
    def sample(self, seeds: Dict[str, np.ndarray],
               exclude_pairs: Optional[Dict[EType, set]] = None
               ) -> MiniBatch:
        """seeds: {ntype: global ids (already padded to a static size)}."""
        exclude_pairs = exclude_pairs or {}
        L = len(self.fanouts)
        frontier: Dict[str, np.ndarray] = {nt: np.asarray(ids, np.int64)
                                           for nt, ids in seeds.items()}
        blocks: List[MFGBlock] = []

        for layer in range(L - 1, -1, -1):
            fan = self.fanouts[layer]
            dst_nodes = frontier
            dst_counts = {nt: len(ids) for nt, ids in dst_nodes.items()}
            # frontier[l-1] build order: self rows first, then per-etype
            parts: Dict[str, List[np.ndarray]] = {nt: [ids]
                                                  for nt, ids in dst_nodes.items()}
            self_offsets = {nt: 0 for nt in dst_nodes}
            edge_blocks: List[EdgeBlockInfo] = []

            for etype in self.g.etypes:
                s, r, d = etype
                if d not in dst_nodes or len(dst_nodes[d]) == 0:
                    continue
                f = fan[etype] if isinstance(fan, dict) else int(fan)
                nbrs, eids, mask = self._sample_neighbors(
                    etype, dst_nodes[d], f, exclude_pairs.get(etype))
                if s not in parts:
                    parts[s] = []
                    self_offsets.setdefault(s, None)
                offset = sum(len(p) for p in parts[s])
                parts[s].append(nbrs.reshape(-1))
                dt = None
                if etype in self.g.edge_times:
                    ts = self.g.edge_times[etype][eids]
                    dt = ts.astype(np.float32)
                edge_blocks.append(EdgeBlockInfo(
                    etype=etype, num_dst=len(dst_nodes[d]), fanout=f,
                    src_offset=offset, mask=mask, nbr_global=nbrs,
                    edge_ids=eids, delta_t=dt))

            src_nodes = {nt: np.concatenate(ps) for nt, ps in parts.items()}
            blocks.append(MFGBlock(
                dst_counts=dst_counts,
                src_counts={nt: len(v) for nt, v in src_nodes.items()},
                self_offsets={nt: off for nt, off in self_offsets.items()
                              if off is not None},
                edge_blocks=edge_blocks,
                src_nodes=src_nodes,
                dst_nodes=dst_nodes,
            ))
            frontier = src_nodes

        blocks.reverse()  # blocks[0] consumes raw features
        return MiniBatch(blocks=blocks, input_nodes=frontier,
                         seeds=seeds, seed_mask={})


# ---------------------------------------------------------------------------
# device-resident sampling (feed mode 3, docs/pipeline.md)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanEdge:
    """Static metadata of one edge block of a planned minibatch."""
    etype: EType
    num_dst: int
    fanout: int
    src_offset: int
    has_delta_t: bool


@dataclasses.dataclass(frozen=True)
class PlanLayer:
    edges: Tuple[PlanEdge, ...]
    dst_counts: Tuple[Tuple[str, int], ...]
    src_counts: Tuple[Tuple[str, int], ...]
    self_offsets: Tuple[Tuple[str, int], ...]
    # frontier build recipe per src ntype, in concatenation order:
    # ("self", ntype) -> the layer's dst rows; ("edge", i) -> edges[i] draws
    parts: Tuple[Tuple[str, Tuple[Tuple[str, int], ...]], ...]


@dataclasses.dataclass(frozen=True)
class SamplePlan:
    """The shapes/offsets side of a device-sampled minibatch.

    Fully determined by (seed counts, fanouts, graph etypes) — the same
    invariant that makes ``BlockSchema`` a jit cache key — and laid out
    *identically* to the host sampler's MFG blocks, so the same
    aggregation (``seg_aggr``) consumes either path.  ``layers[0]``
    consumes raw features (host block order).
    """
    layers: Tuple[PlanLayer, ...]
    seed_counts: Tuple[Tuple[str, int], ...]


def plan_sample(graph: HeteroGraph, fanouts: Sequence,
                seed_counts: Dict[str, int]) -> SamplePlan:
    """Run the host sampler's layer loop symbolically (counts only)."""
    L = len(fanouts)
    frontier = {nt: int(c) for nt, c in seed_counts.items()}
    layers: List[PlanLayer] = []
    for layer in range(L - 1, -1, -1):
        fan = fanouts[layer]
        dst_counts = dict(frontier)
        parts: Dict[str, List[Tuple[str, int]]] = \
            {nt: [("self", 0)] for nt in dst_counts}
        part_counts: Dict[str, List[int]] = \
            {nt: [c] for nt, c in dst_counts.items()}
        self_offsets: Dict[str, Optional[int]] = {nt: 0 for nt in dst_counts}
        edges: List[PlanEdge] = []
        for etype in graph.etypes:
            s, r, d = etype
            if d not in dst_counts or dst_counts[d] == 0:
                continue
            f = fan[etype] if isinstance(fan, dict) else int(fan)
            if s not in part_counts:
                part_counts[s] = []
                parts[s] = []
                self_offsets.setdefault(s, None)
            offset = sum(part_counts[s])
            parts[s].append(("edge", len(edges)))
            part_counts[s].append(dst_counts[d] * f)
            edges.append(PlanEdge(
                etype=etype, num_dst=dst_counts[d], fanout=f,
                src_offset=offset,
                has_delta_t=etype in graph.edge_times))
        src_counts = {nt: sum(cs) for nt, cs in part_counts.items()}
        layers.append(PlanLayer(
            edges=tuple(edges),
            dst_counts=tuple(sorted(dst_counts.items())),
            src_counts=tuple(sorted(src_counts.items())),
            self_offsets=tuple(sorted(
                (nt, off) for nt, off in self_offsets.items()
                if off is not None)),
            parts=tuple(sorted((nt, tuple(p)) for nt, p in parts.items())),
        ))
        frontier = src_counts
    layers.reverse()
    return SamplePlan(layers=tuple(layers),
                      seed_counts=tuple(sorted(
                          (nt, int(c)) for nt, c in seed_counts.items())))


class DeviceNeighborSampler:
    """Fixed-fanout sampler that draws *inside jit* against device CSR.

    The host :class:`NeighborSampler` runs per-batch numpy on the CPU and
    ships index/mask blocks host->device every step; this sampler places
    per-etype ``row_ptr``/``col_idx``/``edge_id`` tables on device once
    (``HeteroGraph.device_csr``) and draws fanout neighbors with
    counter-based ``jax.random`` keys (``repro.kernels.nbr_sample``), so
    sample -> feature gather -> train step fuse into one jitted program
    and a batch ships only int32 seed ids.  The emitted frontier layout
    is byte-identical to the host sampler's (same ``BlockSchema``, same
    mask semantics for zero-degree rows), only the random stream differs.
    """

    def __init__(self, graph: HeteroGraph, fanouts: Sequence, seed: int = 0,
                 use_pallas: bool = False,
                 mesh=None, row_axis: Optional[str] = "data"):
        import jax
        import jax.numpy as jnp
        self.g = graph
        self.fanouts = list(fanouts)
        self.seed = int(seed)
        self.use_pallas = bool(use_pallas)
        self.base_key = jax.random.PRNGKey(self.seed)
        # device tables: one CSR (+ optional edge-time table) per etype;
        # passed into the jitted step as a pytree argument, placed once.
        # With a mesh, tables are row-sharded over ``row_axis`` (memory
        # scales with devices) or replicated when ``row_axis=None`` (the
        # fast data-parallel layout when the adjacency fits per device).
        self.tables = {}
        for et in graph.etypes:
            csr = graph.device_csr(et, mesh=mesh, row_axis=row_axis)
            entry = {"row_ptr": csr.row_ptr, "col_idx": csr.col_idx,
                     "edge_id": csr.edge_id}
            if et in graph.edge_times:
                times = jnp.asarray(graph.edge_times[et], jnp.float32)
                if mesh is not None:
                    from repro.common.sharding import replicate
                    times = replicate(mesh, times)
                entry["times"] = times
            self.tables[et] = entry
        self._plans: Dict[Tuple[Tuple[str, int], ...], SamplePlan] = {}

    def nbytes(self) -> int:
        return sum(int(t.nbytes) for entry in self.tables.values()
                   for t in entry.values())

    # ------------------------------------------------------------------
    def plan_for(self, seed_counts: Dict[str, int]) -> SamplePlan:
        key = tuple(sorted((nt, int(c)) for nt, c in seed_counts.items()))
        if key not in self._plans:
            self._plans[key] = plan_sample(self.g, self.fanouts,
                                           dict(key))
        return self._plans[key]

    # ------------------------------------------------------------------
    def sample(self, tables, plan: SamplePlan, seeds, step,
               exclude=None, dp=None, seed_maps=None, seed_keyed=False,
               shard=None, shard_dedup=False, stats_sink=None):
        """Trace one minibatch draw (call inside jit).

        tables: the sampler's ``.tables`` pytree (passed through the jit
        boundary so the CSR buffers stay arguments, not baked constants);
        seeds: {ntype: (count,) int} matching ``plan.seed_counts``;
        step: traced int32 step counter (the RNG fold-in);
        exclude: optional {etype: (ex_src (E,), ex_dst (E,)) int32} of
        target-edge endpoint pairs, padded with -1 (SpotTarget: sampled
        batch-target edges are masked out; see ``exclusion_pairs``).

        dp: ``(axis_name, num_shards)`` when tracing inside a
        ``shard_map`` over a data mesh.  ``plan``/``seeds`` are then the
        *local* (per-shard) slice of the global batch, and every draw
        consumes the rows of the *global* batch's counter-based bit
        stream that belong to this shard, so the union of all shards'
        draws is bit-identical to the single-device draw (see
        ``_extend_row_map``).

        seed_keyed: draw each frontier row's fanout from a key folded
        with the row's *node id* instead of its batch position (and do
        not fold ``step``).  A row's whole sampled subtree — and hence
        its served embedding — becomes a pure function of its node id,
        invariant to batch composition, padding, request splitting, and
        replica routing.  This is the serving determinism mode
        (``DeviceInferProgram``; docs/serving.md); it is mutually
        exclusive with ``dp``, whose bit-stream contract is positional.

        shard: ``(axis_name, n_shards)`` when the CSR ``col_idx``/
        ``edge_id`` tables are *row-sharded* over the mesh axis (so each
        shard_map body sees only its local block).  The draw then splits
        into position math against the replicated ``row_ptr`` plus a
        :class:`repro.common.sharding.RaggedExchange` that pulls exactly
        the drawn entries from their owning shards — the same bit stream
        and positions as the replicated draw, so results stay
        bit-identical.  Composes with ``dp`` (which governs whose rows of
        the global bit stream this shard consumes).

        shard_dedup: with ``shard``, route the drawn positions through
        ``sharding.dedup_gather`` — same results; whether the layer
        actually compacts is dedup_gather's static payload-width call
        (the 8 B ``(col, eid)`` pair sits under
        ``DEDUP_MIN_PAYLOAD_BYTES``, so CSR draws currently resolve to
        the plain exchange).  ``stats_sink``: optional list the sharded
        draw appends per-exchange measured stats to (the exchange-bytes
        probe; see ``dedup_gather``).

        seed_maps: optional ``{ntype: (base, stride)}`` trace-time numpy
        local->global row maps of the *seed* block itself, for dp runs
        whose seed layout concatenates several roles per ntype (edge
        src/dst endpoints, LP positives + negatives — see
        ``TaskProgram.seed_maps``): local seed row ``p`` of a shard sits
        at global row ``base[p] + shard * stride[p]``.  Defaults to the
        single-role map (contiguous ``count``-row slices per shard).

        Returns (masks, delta_t, frontier): per-layer {ekey: (n, f)} bool
        masks and float Δt dicts in block order (``[0]`` consumes raw
        features), and the frontier[0] int32 ids per ntype — everything
        the GNN apply + in-jit feature gather need.
        """
        import jax
        import jax.numpy as jnp
        frontier = {nt: jnp.asarray(seeds[nt]).astype(jnp.int32)
                    for nt, _ in plan.seed_counts}
        from repro.kernels.nbr_sample import nbr_sample
        from repro.trainer import tracing
        if seed_keyed and dp is not None:
            raise ValueError("seed_keyed draws and dp sharding are "
                             "mutually exclusive — the dp bit-stream "
                             "contract is positional")
        if dp is not None:
            axis_name, n_shards = dp
            shard_idx = jax.lax.axis_index(axis_name)
            # local row p of the per-ntype frontier sits at global row
            # base[p] + shard * stride[p] (affine; numpy, trace-time)
            maps = seed_maps if seed_maps is not None else \
                {nt: (np.arange(c, dtype=np.int64),
                      np.full(c, c, np.int64))
                 for nt, c in plan.seed_counts}
        layer_masks: List[Dict[str, object]] = []
        layer_dts: List[Dict[str, object]] = []
        # sampling walks top (seeds) -> bottom; plan stores block order
        for li, pl_layer in enumerate(reversed(plan.layers)):
            draws = []
            masks: Dict[str, object] = {}
            dts: Dict[str, object] = {}
            for ei, pe in enumerate(pl_layer.edges):
                t = tables[pe.etype]
                key = jax.random.fold_in(
                    jax.random.fold_in(self.base_key,
                                       0 if seed_keyed else step),
                    li * 131071 + ei)
                dst_ids = frontier[pe.etype[2]]
                bits = None
                if seed_keyed:
                    # one key per frontier *node id*: the draw no longer
                    # depends on the row's position or the step counter,
                    # so a node's fanout — and recursively its whole
                    # subtree — is identical in any batch that contains it
                    row_keys = jax.vmap(jax.random.fold_in,
                                        in_axes=(None, 0))(key, dst_ids)
                    bits = jax.vmap(
                        lambda k: jax.random.bits(k, (pe.fanout,),
                                                  jnp.uint32))(row_keys)
                if dp is not None:
                    # generate the global batch's bits (cheap, counter-
                    # based, identical on every shard) and keep our rows
                    base, stride = maps[pe.etype[2]]
                    rows = jnp.asarray(base) + \
                        shard_idx * jnp.asarray(stride)
                    bits = jax.random.bits(
                        key, (pe.num_dst * n_shards, pe.fanout),
                        jnp.uint32)[rows]
                if shard is not None:
                    nbr, eid, mask = _nbr_sample_sharded(
                        t["row_ptr"], t["col_idx"], t["edge_id"], dst_ids,
                        key, fanout=pe.fanout, bits=bits, shard=shard,
                        dedup=shard_dedup, stats_sink=stats_sink)
                else:
                    nbr, eid, mask = nbr_sample(
                        t["row_ptr"], t["col_idx"], t["edge_id"], dst_ids,
                        key, fanout=pe.fanout, use_pallas=self.use_pallas,
                        bits=bits)
                if exclude is not None and pe.etype in exclude:
                    with tracing.scope("spot_target"):
                        hit = _pair_exclusion_hit(nbr, dst_ids,
                                                  *exclude[pe.etype])
                        mask = mask & ~hit
                ek = "___".join(pe.etype)
                masks[ek] = mask
                if pe.has_delta_t:
                    dts[ek] = jnp.take(t["times"], eid.reshape(-1),
                                       axis=0).reshape(eid.shape)
                draws.append(nbr)
            new_frontier = {}
            new_maps = {}
            for nt, recipe in pl_layer.parts:
                arrs = [frontier[nt] if kind == "self"
                        else draws[idx].reshape(-1)
                        for kind, idx in recipe]
                new_frontier[nt] = (jnp.concatenate(arrs)
                                    if len(arrs) > 1 else arrs[0])
                if dp is not None:
                    new_maps[nt] = _extend_row_map(
                        maps, pl_layer, nt, recipe, n_shards)
            layer_masks.append(masks)
            layer_dts.append(dts)
            frontier = new_frontier
            if dp is not None:
                maps = new_maps
        layer_masks.reverse()
        layer_dts.reverse()
        return layer_masks, layer_dts, frontier


def _nbr_sample_sharded(row_ptr, col_idx_local, edge_id_local, dst_ids, key,
                        *, fanout, bits, shard, dedup=False,
                        stats_sink=None):
    """The ``nbr_sample`` draw against *row-sharded* CSR tables.

    ``row_ptr`` is replicated, so each shard computes the exact same edge
    positions the replicated oracle would (same bits, same modulo draw,
    same clip); only the gather differs — the drawn positions are pulled
    from their owning shards through one
    :class:`~repro.common.sharding.RaggedExchange`, with ``col_idx`` and
    ``edge_id`` stacked into a single payload so the drawn entries cross
    shards in one collective instead of all-gathering table slices.  Must
    be traced inside ``shard_map`` over the axis in ``shard``.

    With-replacement draws repeat positions (guaranteed whenever a row's
    degree is below the fanout, and often otherwise); ``dedup`` routes
    them through :func:`~repro.common.sharding.dedup_gather`, whose
    static payload-width policy decides whether the layer compacts —
    the 8 B ``(col, eid)`` pair sits under ``DEDUP_MIN_PAYLOAD_BYTES``,
    so the draw currently keeps the plain wire and the dedup win comes
    from the wide feature rows — bit-identical either way.
    """
    import jax
    import jax.numpy as jnp
    from repro.common.sharding import (RaggedExchange, dedup_gather,
                                       unique_count)
    from repro.kernels.nbr_sample import segment_bounds_ref
    axis_name, n_shards = shard
    dst_ids = dst_ids.astype(jnp.int32)
    n = dst_ids.shape[0]
    starts, degs = segment_bounds_ref(row_ptr, dst_ids)
    if bits is None:
        bits = jax.random.bits(key, (n, fanout), jnp.uint32)
    deg_u = jnp.maximum(degs, 1).astype(jnp.uint32)
    draw = (bits % deg_u[:, None]).astype(jnp.int32)
    local_e = col_idx_local.shape[0]
    flat = jnp.clip(starts[:, None] + draw, 0, local_e * n_shards - 1)
    ids = flat.reshape(-1)
    # one payload exchange for both tables: stack (col, eid) per edge so
    # the drawn entries cross shards in a single collective
    pair = jnp.stack([col_idx_local.astype(jnp.int32),
                      edge_id_local.astype(jnp.int32)], axis=1)
    if dedup:
        got = dedup_gather(ids, pair, axis_name=axis_name,
                           n_shards=n_shards, rows_per_shard=local_e,
                           stats_sink=stats_sink)
    else:
        if stats_sink is not None:
            stats_sink.append({"requests": ids.shape[0],
                               "distinct": unique_count(ids),
                               "capacity": ids.shape[0],
                               "payload_bytes": 8,    # (col, eid) int32
                               "fits": jnp.int32(1)})
        ex = RaggedExchange(ids, axis_name=axis_name, n_shards=n_shards,
                            rows_per_shard=local_e)
        got = ex.gather(pair)
    got = got.reshape(n, fanout, 2)
    nbr, eid = got[..., 0], got[..., 1]
    mask = jnp.broadcast_to((degs > 0)[:, None], (n, fanout))
    return nbr, eid, mask


def _pair_exclusion_hit(nbr, dst_ids, ex_src, ex_dst):
    """In-jit SpotTarget membership test: which sampled edges
    ``(nbr[i, j], dst_ids[i])`` coincide with an excluded
    ``(ex_src, ex_dst)`` target pair.

    Exact membership by compares alone, O(n * f * E) of them and no
    gather, sort or search: the ``n * f`` queries lie flat on the minor
    (lane) axis and the E pairs on the major one, which the reduction
    ORs away elementwise, so on a TPU the ``(E, n * f)`` compare fuses
    into the reduction and never reaches HBM.  Padding pairs of ``-1``
    match no node id, duplicate pairs are harmless, and E = 0 gives all
    false.
    """
    import jax.numpy as jnp
    n, f = nbr.shape
    q_src = nbr.reshape(-1)
    q_dst = jnp.broadcast_to(dst_ids[:, None], (n, f)).reshape(-1)
    hit = (ex_src[:, None] == q_src[None, :]) \
        & (ex_dst[:, None] == q_dst[None, :])
    return hit.any(axis=0).reshape(n, f)


def _extend_row_map(maps, pl_layer: PlanLayer, nt: str, recipe,
                    n_shards: int):
    """Affine local->global row map of the next (local) frontier.

    The global frontier is the concatenation of global parts; each part's
    global length is ``n_shards`` times its local length, and within a
    part the local rows of shard ``s`` sit at ``s * local_len`` (self
    parts inherit the dst frontier's map; draw parts expand it by the
    fanout).  Everything here is trace-time numpy — only the shard index
    is traced, as the coefficient of ``stride``.
    """
    def part_len(kind, idx):
        if kind == "self":
            return len(maps[nt][0])
        pe = pl_layer.edges[idx]
        return pe.num_dst * pe.fanout

    bases, strides = [], []
    off_g = 0
    for kind, idx in recipe:
        length = part_len(kind, idx)
        if kind == "self":
            base, stride = maps[nt]
            bases.append(off_g + base)
            strides.append(stride)
        else:
            pe = pl_layer.edges[idx]
            base_d, stride_d = maps[pe.etype[2]]
            pd = np.arange(length) // pe.fanout
            j = np.arange(length) % pe.fanout
            bases.append(off_g + base_d[pd] * pe.fanout + j)
            strides.append(stride_d[pd] * pe.fanout)
        off_g += length * n_shards
    return (np.concatenate(bases) if len(bases) > 1 else bases[0],
            np.concatenate(strides) if len(strides) > 1 else strides[0])


def shard_host_perms(local_plan: SamplePlan, local_role_list,
                     n_shards: int):
    """Shard-major row permutations of a *host-sampled* global MFG.

    The data-parallel shard_map lowering hands each shard the contiguous
    ``1/n`` slice of every seed role plus exactly the frontier rows its
    seeds expand to — the affine decomposition ``_extend_row_map`` builds
    for device-sampled dp.  This mirrors that recursion in plain numpy
    over the *local* plan (the per-shard seed layout): for each layer's
    dst frontier, and for the input frontier, it returns a permutation
    such that ``global_rows[perm]`` is shard-major — slicing the permuted
    array into ``n_shards`` equal blocks yields every shard's local
    frontier in local-plan row order.  Everything is static per schema;
    apply once per stacked epoch with fancy indexing.

    local_role_list: ``[(ntype, local_rows), ...]`` in role declaration
    order (the per-shard seed layout, global role length // n_shards).

    Returns ``(dst_perms, input_perms)``: ``dst_perms[li][nt]`` permutes
    the dst rows of ``local_plan.layers[li]`` scaled to global counts
    (the rows that layer's masks/Δt index); ``input_perms[nt]`` permutes
    the input frontier (the feature / index rows).
    """
    per_nt: Dict[str, List[int]] = {}
    for nt, c in local_role_list:
        per_nt.setdefault(nt, []).append(int(c))
    maps: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    for nt, lens in per_nt.items():
        bases, strides, off_g = [], [], 0
        for c in lens:
            bases.append(off_g + np.arange(c, dtype=np.int64))
            strides.append(np.full(c, c, np.int64))
            off_g += c * n_shards
        maps[nt] = (
            np.concatenate(bases) if len(bases) > 1 else bases[0],
            np.concatenate(strides) if len(strides) > 1 else strides[0])

    def perm(m):
        base, stride = m
        return np.concatenate([base + s * stride for s in range(n_shards)])

    n_layers = len(local_plan.layers)
    dst_perms: List[Dict[str, np.ndarray]] = [None] * n_layers
    for li in range(n_layers - 1, -1, -1):
        pl_layer = local_plan.layers[li]
        dst_perms[li] = {nt: perm(maps[nt])
                         for nt, _ in pl_layer.dst_counts}
        maps = {nt: _extend_row_map(maps, pl_layer, nt, recipe, n_shards)
                for nt, recipe in pl_layer.parts}
    return dst_perms, {nt: perm(m) for nt, m in maps.items()}


def exclusion_pairs(src: np.ndarray, dst: np.ndarray,
                    pad_to: Optional[int] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """(src, dst) target-edge endpoints for the device sampler's
    exclusion mask, padded with -1 (matches no sampled edge; int32-safe
    at any graph scale, unlike a combined src*|V|+dst code)."""
    src = src.astype(np.int32)
    dst = dst.astype(np.int32)
    if pad_to is not None and len(src) < pad_to:
        fill = np.full(pad_to - len(src), -1, np.int32)
        src = np.concatenate([src, fill])
        dst = np.concatenate([dst, fill])
    return src, dst


def pad_seeds(ids: np.ndarray, batch_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a seed array to a static batch size; returns (padded, mask)."""
    n = len(ids)
    assert n <= batch_size
    out = np.zeros(batch_size, np.int64)
    out[:n] = ids
    mask = np.zeros(batch_size, bool)
    mask[:n] = True
    return out, mask


def fetch_features(graph: HeteroGraph, nodes: Dict[str, np.ndarray],
                   feat_name: str = "feat") -> Dict[str, np.ndarray]:
    """Gather raw input features for frontier[0] (the RPC 'pull' in
    DistDGL; a sharded gather in the JAX engine)."""
    out = {}
    for nt, ids in nodes.items():
        f = graph.node_feats.get(nt, {}).get(feat_name)
        if f is not None:
            out[nt] = f[ids]
    return out
