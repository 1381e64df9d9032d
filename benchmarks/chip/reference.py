"""Plain RGCN training reference: the semantics the benchmark holds the
program to, written from the description below in straightforward
``jax.numpy``.  It imports nothing of the program.

- Adjacency: for each relation, the in-edges of every destination node,
  in edge-list order.
- Draws: at step ``t``, layer ``li`` (0 is the seeds' layer) and relation
  ``ei`` (its index among the relations sampled in that layer, relations
  in sorted order), ``key = fold_in(fold_in(PRNGKey(seed), t), li * 131071
  + ei)`` and ``bits = random.bits(key, (rows, fanout), uint32)``; slot
  ``j`` of row ``i`` is in-neighbour ``bits[i, j] % deg_i`` (with
  replacement) and is masked when ``deg_i == 0``.
- Frontier: per node type, the layer's own rows first, then each
  relation's draws, relations in sorted order.
- RGCN layer: ``h'_v = b + h_v W_self + sum_r mean_{unmasked draws u of
  r}(h_u) W_r``, ReLU between layers; the input encoder is ``ReLU(x W +
  b)`` per node type, ``x`` a feature row or a learnable embedding row.
- Node classification: a two-layer ReLU MLP, softmax cross-entropy over
  the unmasked seeds.
- Link prediction: DistMult with the target relation's vector; ``k``
  shared negatives per group of ``k`` positives, ``bits((B/k, k)) %
  num_dst`` under ``fold_in(fold_in(PRNGKey(seed), t), 0x5EED0000)``;
  contrastive loss at a temperature; a sampled edge equal to one of the
  batch's target pairs, in either direction, is masked.
- AdamW without decay, learning rate warmed up linearly then on a cosine;
  sparse adagrad on the embedding rows, duplicate rows summed.

Element-wise arithmetic is float32.  Weights, tables and features are
stored as ``storage`` (float32 as configured; ``"bfloat16"`` rounds them
after every update, one step below).  Every matrix product, in the
forward and the backward pass, rounds its two inputs to ``products`` and
accumulates in float32 (exactly, at ``highest`` precision):
``"bfloat16"`` is the one-pass product a TPU runs for float32 at its
default precision, ``"float32"`` keeps the inputs whole, and the type
one step below the configuration's gives the control.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EType = Tuple[str, str, str]
NEG_STREAM = 0x5EED0000
LAYER_STRIDE = 131071
# faults the reference can carry in the program's place, to read how far
# each moves the compared numbers: the step's state returned unchanged;
# the loss over half the batch; each featured input row read from its
# neighbour's slot; the embedding tables left out of the update
FAULTS = (None, "unchanged", "half_batch", "altered", "frozen_tables")


def ekey(et: EType) -> str:
    return "___".join(et)


def leaf_names(tree) -> list:
    """``a/b/0/c`` for each leaf of ``tree``, in flattening order."""
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@dataclasses.dataclass(frozen=True)
class Layer:
    """One message-passing layer: destination rows per node type, the
    relations sampled into it ``(etype, rows, fanout, src_offset)`` and
    the source frontier's rows per node type."""
    dst: Tuple[Tuple[str, int], ...]
    edges: Tuple[Tuple[EType, int, int, int], ...]
    src: Tuple[Tuple[str, int], ...]


def plan(etypes: Sequence[EType], fanouts: Sequence[int],
         seed_counts: Dict[str, int]) -> Tuple[Layer, ...]:
    """Frontier sizes of a minibatch, first layer (raw inputs) first."""
    etypes = sorted(tuple(e) for e in etypes)
    frontier = {nt: int(c) for nt, c in seed_counts.items() if c}
    layers = []
    for f in reversed(list(fanouts)):
        counts = dict(frontier)
        edges = []
        for et in etypes:
            n = frontier.get(et[2], 0)
            if n == 0:
                continue
            edges.append((et, n, int(f), counts.get(et[0], 0)))
            counts[et[0]] = counts.get(et[0], 0) + n * int(f)
        layers.append(Layer(dst=tuple(sorted(frontier.items())),
                            edges=tuple(edges),
                            src=tuple(sorted(counts.items()))))
        frontier = counts
    return tuple(reversed(layers))


def build_csr(edges: Dict[EType, Tuple[np.ndarray, np.ndarray]],
              num_nodes: Dict[str, int], drop: Dict[EType, np.ndarray] = None):
    """Per relation ``(row_ptr, col)`` on the device: the in-edges of each
    destination in edge-list order; ``drop`` masks edges out."""
    drop = drop or {}
    out = {}
    for et, (src, dst) in edges.items():
        keep = ~drop[et] if et in drop else None
        s = src if keep is None else src[keep]
        d = dst if keep is None else dst[keep]
        s = jnp.asarray(s.astype(np.int32))
        d = jnp.asarray(d.astype(np.int32))
        order = jnp.argsort(d, stable=True)
        counts = jnp.zeros(num_nodes[et[2]], jnp.int32).at[d].add(1)
        row_ptr = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(counts)])
        out[et] = {"row_ptr": row_ptr, "col": s[order]}
    return out


def _pair_hit(nbr, dst, ex_src, ex_dst, block: int = 256):
    """``hit[i, j]``: (nbr[i, j], dst[i]) is one of the pairs, by a plain
    compare against every pair, ``block`` rows at a time."""
    n, f = nbr.shape
    pad = -n % block
    nb = jnp.pad(nbr, ((0, pad), (0, 0)), constant_values=-1)
    ds = jnp.pad(dst, (0, pad), constant_values=-1)

    def one(args):
        nbr_b, dst_b = args
        same_dst = dst_b[:, None] == ex_dst[None, :]            # (b, E)
        same_src = nbr_b[:, :, None] == ex_src[None, None, :]   # (b, f, E)
        return (same_src & same_dst[:, None, :]).any(-1)
    hit = jax.lax.map(one, (nb.reshape(-1, block, f), ds.reshape(-1, block)))
    return hit.reshape(-1, f)[:n]


def sample(csr, layers, seeds, step, seed, exclude=None):
    """Masks per layer (first layer first) and the input frontier."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    frontier = {nt: seeds[nt].astype(jnp.int32) for nt in seeds}
    masks = [None] * len(layers)
    for li, layer in enumerate(reversed(layers)):
        draws, m = {}, {}
        for ei, (et, n, f, _) in enumerate(layer.edges):
            key = jax.random.fold_in(base, li * LAYER_STRIDE + ei)
            dst = frontier[et[2]]
            row_ptr, col = csr[et]["row_ptr"], csr[et]["col"]
            start = row_ptr[dst]
            deg = row_ptr[dst + 1] - start
            bits = jax.random.bits(key, (n, f), jnp.uint32)
            off = (bits % jnp.maximum(deg, 1).astype(jnp.uint32)[:, None])
            pos = jnp.minimum(start[:, None] + off.astype(jnp.int32),
                              col.shape[0] - 1)
            mask = jnp.broadcast_to((deg > 0)[:, None], (n, f))
            if exclude is not None and et in exclude:
                mask = mask & ~_pair_hit(col[pos], dst, *exclude[et])
            draws[et] = col[pos]
            m[ekey(et)] = mask
        dst_nts = dict(layer.dst)
        new = {}
        for nt, _ in layer.src:
            parts = [frontier[nt]] if nt in dst_nts else []
            parts += [draws[et].reshape(-1) for et, *_ in layer.edges
                      if et[0] == nt]
            new[nt] = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        masks[len(layers) - 1 - li] = m
        frontier = new
    return masks, frontier


def matmul(products: str):
    """``a @ b`` with both inputs rounded to ``products``, accumulated in
    float32 at highest precision."""
    hi = jax.lax.Precision.HIGHEST
    dt = jnp.dtype(products)

    def rnd(x):
        return x.astype(dt).astype(jnp.float32)
    return lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=hi)


def embed(params, layers, masks, x, mm):
    """Seed-layer embeddings of the RGCN stack over one minibatch."""
    relu = jax.nn.relu
    h = {nt: relu(mm(v, params["gnn"]["input"][nt]["w"])
                  + params["gnn"]["input"][nt]["b"]) for nt, v in x.items()}
    for li, layer in enumerate(layers):
        p = params["gnn"]["layers"][li]
        out = {}
        for nt, n in layer.dst:
            acc = mm(h[nt][:n], p["w_self"][nt]) + p["b"][nt]
            for et, nd, f, off in layer.edges:
                if et[2] != nt:
                    continue
                rows = h[et[0]][off:off + nd * f].reshape(nd, f, -1)
                w = masks[li][ekey(et)].astype(rows.dtype)
                agg = (rows * w[..., None]).sum(1) / jnp.maximum(
                    w.sum(1), 1)[:, None]
                acc = acc + mm(agg, p["w_rel"][ekey(et)])
            out[nt] = acc
        h = out if li == len(layers) - 1 else {nt: relu(v)
                                               for nt, v in out.items()}
    return h


def nc_loss(params, h, target, labels, mask, mm):
    d = params["dec"]
    z = mm(jax.nn.relu(mm(h[target], d["w1"]) + d["b1"]), d["w2"]) + d["b2"]
    ls = jax.nn.log_softmax(z.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(ls, labels[:, None].astype(jnp.int32), -1)[:, 0]
    m = mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)


def lp_loss(params, h, roles, rel_idx, k, temperature, keep=None):
    (snt, so, B), (dnt, do, _), (nnt, no, nn) = roles
    src, dst = h[snt][so:so + B], h[dnt][do:do + B]
    neg = h[nnt][no:no + nn]
    rel = params["dec"]["rel"][rel_idx]
    pos = (src * rel * dst).sum(-1)
    if k >= B:
        nsc = (src[:, None, :] * rel * neg[None, :, :]).sum(-1)
    else:
        g = B // k
        nsc = (src.reshape(g, k, 1, -1) * rel
               * neg.reshape(g, 1, k, -1)).sum(-1).reshape(B, k)
    logits = jnp.concatenate([pos[:, None], nsc], 1).astype(jnp.float32)
    ll = jax.nn.log_softmax(logits / temperature, axis=1)[:, 0]
    if keep is None:
        return -ll.mean()
    return -(ll * keep).sum() / keep.sum()


def lr_at(step, opt):
    """Linear warm-up to ``lr`` over ``warmup`` steps, then a cosine to
    ``floor * lr`` at ``total``."""
    lr, warm, total = opt["lr"], opt["warmup"], opt["total"]
    s = step.astype(jnp.float32)
    t = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = lr * (opt["floor"] + (1 - opt["floor"]) * 0.5
                * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(s < warm, lr * jnp.minimum(1.0, (s + 1) / warm), cos)


def adamw(params, grads, state, step, opt):
    t = (step + 1).astype(jnp.float32)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr = lr_at(step, opt)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               state["v"], grads)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t))
        / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, m, v)
    return new, {"m": m, "v": v}


def adagrad(table, gsum, summed, lr, eps):
    """One adagrad step of every row; ``summed`` is the table-shaped
    gradient (zero in untouched rows, which stay as they are)."""
    gsum = gsum + (summed * summed).sum(1)
    return table - (lr / (jnp.sqrt(gsum) + eps))[:, None] * summed, gsum


class Reference:
    """The first steps of one run of a configuration, from the same
    weights, tables, features and seed blocks the program was given."""

    def __init__(self, cfg: dict, graph, batch_size: int, products=None,
                 storage=None, fault=None):
        gs = cfg["gs"]
        self.task = gs["task"]
        self.opt = dict(cfg["reference"]["optimizer"],
                        lr=gs["hyperparam"]["lr"])
        self.seed = int(gs["hyperparam"]["seed"])
        self.mm = matmul(products or cfg["reference"]["products"])
        sdt = jnp.dtype(storage or cfg["reference"]["storage"])
        self.store = lambda x: x.astype(sdt).astype(jnp.float32)  # noqa: E731
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
        self.fault = fault
        self.B = int(batch_size)
        etypes = sorted(graph.edges)
        fan = gs["gnn"]["fanout"]
        if self.task == "node_classification":
            self.target = gs["node_classification"]["target_ntype"]
            seeds = {self.target: self.B}
        else:
            lp = gs["link_prediction"]
            s, r, d = self.etype = tuple(lp["target_etype"])
            self.k = int(lp["num_negatives"])
            self.temperature = float(cfg["reference"]["temperature"])
            self.n_neg = self.B if self.k < self.B else self.k
            counts = {}
            self.roles = []
            for nt, n in ((s, self.B), (d, self.B), (d, self.n_neg)):
                self.roles.append((nt, counts.get(nt, 0), n))
                counts[nt] = counts.get(nt, 0) + n
            seeds = counts
            self.rel_idx = [ekey(e) for e in etypes].index(ekey(self.etype))
            self.num_dst = graph.num_nodes[d]
        self.layers = plan(etypes, fan, seeds)

    # -- one step ---------------------------------------------------------
    def _seeds(self, blocks, step):
        if self.task == "node_classification":
            return {self.target: blocks["seeds"]}, None
        s, r, d = self.etype
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(self.seed), step), NEG_STREAM)
        g = -(-self.B // self.k)
        shared = (jax.random.bits(key, (g, self.k), jnp.uint32)
                  % jnp.uint32(self.num_dst)).astype(jnp.int32)
        neg = shared.reshape(-1)[:self.n_neg]
        src = blocks["src"].astype(jnp.int32)
        dst = blocks["dst"].astype(jnp.int32)
        seeds = {}
        for (nt, _, _), ids in zip(self.roles, (src, dst, neg)):
            seeds[nt] = (jnp.concatenate([seeds[nt], ids]) if nt in seeds
                         else ids)
        exclude = {self.etype: (src, dst), (d, r + "-rev", s): (dst, src)}
        return seeds, exclude

    def step(self, state, csr, feats, labels, blocks, step):
        params, opt_state, tables, gsums = state
        seeds, exclude = self._seeds(blocks, step)
        masks, frontier = sample(csr, self.layers, seeds, step, self.seed,
                                 exclude)
        rows_of = (lambda ids: jnp.roll(ids, 1)) if self.fault == "altered" \
            else (lambda ids: ids)
        x_feat = {nt: feats[nt][rows_of(frontier[nt])] for nt in feats}
        x_rows = {nt: tables[nt][frontier[nt]] for nt in tables}
        keep = None
        if self.fault == "half_batch":
            keep = (jnp.arange(self.B) < self.B // 2).astype(jnp.float32)

        def loss_of(p, rows):
            h = embed(p, self.layers, masks, {**x_feat, **rows}, self.mm)
            if self.task == "node_classification":
                mask = blocks["seed_mask"]
                if keep is not None:
                    mask = mask & (keep > 0)
                return nc_loss(p, h, self.target,
                               labels[self.target][blocks["seeds"]], mask,
                               self.mm)
            return lp_loss(p, h, self.roles, self.rel_idx, self.k,
                           self.temperature, keep)

        loss, (gp, grows) = jax.value_and_grad(loss_of, argnums=(0, 1))(
            params, x_rows)
        summed = {nt: jnp.zeros_like(tables[nt]).at[frontier[nt]].add(
            grows[nt]) for nt in tables}
        norms = {"params": jax.tree_util.tree_map(jnp.linalg.norm, gp),
                 "tables": {nt: jnp.linalg.norm(g) for nt, g in summed.items()}}
        if self.fault == "unchanged":
            return state, loss, norms
        params, opt_state = adamw(params, gp, opt_state, step, self.opt)
        params = jax.tree_util.tree_map(self.store, params)
        tables, gsums = dict(tables), dict(gsums)
        for nt in tables if self.fault != "frozen_tables" else ():
            t, gsums[nt] = adagrad(tables[nt], gsums[nt], summed[nt],
                                   self.opt["sparse_lr"],
                                   self.opt["sparse_eps"])
            tables[nt] = self.store(t)
        return (params, opt_state, tables, gsums), loss, norms

    def run(self, params, tables, csr, feats, labels, blocks, steps: int):
        """``steps`` steps from the given weights and tables: the loss of
        each, the norm of each leaf's gradient in the first (``params``
        and ``tables``, as the optimizers get it), and the final weights
        and tables."""
        st = self.store
        params = jax.tree_util.tree_map(st, params)
        tables = {nt: st(t) for nt, t in tables.items()}
        feats = {nt: st(f) for nt, f in feats.items()}
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        state = (params, {"m": zeros, "v": zeros}, tables,
                 {nt: jnp.zeros(t.shape[0], jnp.float32)
                  for nt, t in tables.items()})
        fn = jax.jit(self.step)
        losses, norms = [], None
        for t in range(steps):
            b = {k: jnp.asarray(v[t]) for k, v in blocks.items()}
            state, loss, n = fn(state, csr, feats, labels, b,
                                jnp.asarray(t, jnp.int32))
            losses.append(loss)
            norms = n if norms is None else norms
        return {"losses": [float(x) for x in losses],
                "grad_norms": jax.tree_util.tree_map(float, norms),
                "params": state[0], "tables": state[2]}
