"""Trainers / predictors (paper §3.1.3).

A trainer owns: the GNN model params, the task decoder, optional sparse
embedding tables for featureless node types, one jitted step per
BlockSchema (schemas are static per loader config, so in practice one),
and an evaluator.  The same trainer runs on one device or a data mesh
(GraphStorm's "no code change across hardware" property): pass ``mesh=``
a 1-D ``("data",)`` mesh (``launch.mesh.make_data_mesh``) and the device
step runs data-parallel — batches shard over the mesh, dense params
replicate with mean-all-reduced gradients, and the loss/metrics keep
their global-batch semantics (docs/pipeline.md §3d).  With replicated
tables the step is an explicit ``shard_map`` (per-shard local programs,
bit-identical sample stream to the 1-device run); with row-sharded
tables (``shard_tables``) it is also a ``shard_map``, where every table
gather and the sparse gradient scatter-back go through an explicit
ragged all-to-all exchange (``shard_gather: alltoall``, the default —
shards ship only the rows others drew) and the epoch scan prefetches
batch k+1's row exchanges under batch k's compute
(``remote_prefetch``).  ``shard_gather: gspmd`` keeps the legacy
sharding-annotated-jit lowering, where GSPMD turns cross-shard gathers
into blanket collectives.

Device-resident pipeline (docs/pipeline.md): pass ``feature_store=``
a ``repro.core.feature_store.DeviceFeatureStore`` and pair it with loaders
built with ``host_features=False``.  Raw-feature gathers then happen
*inside* the jitted step from device-resident tables, so a batch ships
only int32 index blocks and bool masks host->device.  The step donates
params/opt_state buffers on backends that support donation (in-place
updates, no copy of the model per step).

The fully-jitted device step (feed mode 3) is *task-agnostic*: this
module owns the engine (sampling, gathers, optimizers, scanned epochs,
both data-parallel lowerings) and dispatches everything task-specific —
seed layout, in-jit negative draws, the loss head — to the task's
``TaskProgram`` (``repro.trainer.task_programs``).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import SparseEmbedding
from repro.core.lp import (contrastive_lp_loss, cross_entropy_lp_loss, mrr)
from repro.gnn.decoders import (decoder_apply, init_decoder, lp_score,
                                lp_score_all)
from repro.gnn.model import GSgnnModel, gnn_apply_blocks, init_gnn_model
from repro.optim import adamw
from repro.optim.schedules import cosine_schedule
from repro.trainer import tracing

# device-resident validation draws its sampling steps from a dedicated
# range of the counter-based stream so eval subgraphs never collide with
# (or perturb) the training step counter
_EVAL_STEP_BASE = 1 << 30


def _xent(logits, labels, mask):
    ls = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(ls, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)


def _mse(preds, labels, mask):
    se = (preds.reshape(-1) - labels.reshape(-1).astype(jnp.float32)) ** 2
    m = mask.astype(jnp.float32)
    return (se * m).sum() / jnp.maximum(m.sum(), 1.0)


def _sparse_adagrad_dp(table, gsum, ids, grad_rows, lr, axis_name):
    """Data-parallel sparse adagrad (inside shard_map, replicated table):
    every shard scatters its local (ids, grad_rows) into a table-shaped
    buffer, a psum makes it the *global* duplicate-summed gradient, and
    each shard then applies the identical update — the same semantics as
    ``_sparse_adagrad``'s dense lowering with dedupe across the whole
    global batch."""
    summed = jnp.zeros_like(table).at[ids].add(grad_rows.astype(table.dtype))
    summed = jax.lax.psum(summed, axis_name)
    gnorm = jnp.sum(summed.astype(jnp.float32) ** 2, axis=1)
    gsum = gsum + gnorm          # untouched rows: gnorm == 0, unchanged
    scale = lr / (jnp.sqrt(gsum) + 1e-10)
    return table - (scale[:, None] * summed).astype(table.dtype), gsum


def _sparse_adagrad_shard(table, gsum, ex, grad_rows, lr):
    """Sparse adagrad for a *row-sharded* table inside shard_map: each
    request's gradient row is routed to the shard owning that row through
    the presampled :class:`~repro.common.sharding.RaggedExchange` (the
    reverse of the forward gather), scatter-added into a local-block-shaped
    buffer (duplicate ids sum — the local block of exactly the global
    duplicate-summed gradient ``_sparse_adagrad_dp`` builds), and the
    identical adagrad update applied to the owned rows.  No psum: every
    row has exactly one owner."""
    payload, local_ids, mask = ex.scatter_rows(grad_rows)
    rows = jnp.where(mask[..., None], payload, 0).astype(table.dtype)
    summed = jnp.zeros_like(table).at[local_ids.reshape(-1)].add(
        rows.reshape((-1,) + rows.shape[2:]))
    gnorm = jnp.sum(summed.astype(jnp.float32) ** 2, axis=1)
    gsum = gsum + gnorm          # untouched rows: gnorm == 0, unchanged
    scale = lr / (jnp.sqrt(gsum) + 1e-10)
    return table - (scale[:, None] * summed).astype(table.dtype), gsum


def _sparse_adagrad(table, gsum, ids, grad_rows, lr):
    """In-jit sparse adagrad with ``SparseEmbedding.apply_sparse_grad``'s
    exact semantics: dedupe ids, sum duplicate-row grads, one adagrad
    step per unique row, untouched rows untouched.  Two equivalent
    lowerings, picked on static shapes: a dense table-shaped scatter
    when the table is minibatch-sized (cheapest — no sort), and an
    O(frontier) sort + segment-sum + row scatter when the table dwarfs
    the frontier, so the step never scales with total embedding rows."""
    if table.shape[0] <= 4 * ids.shape[0]:
        summed = jnp.zeros_like(table).at[ids].add(
            grad_rows.astype(table.dtype))
        gnorm = jnp.sum(summed.astype(jnp.float32) ** 2, axis=1)
        gsum = gsum + gnorm      # untouched rows: gnorm == 0, unchanged
        scale = lr / (jnp.sqrt(gsum) + 1e-10)
        return table - (scale[:, None] * summed).astype(table.dtype), gsum
    n = ids.shape[0]
    order = jnp.argsort(ids)
    sid = ids[order]
    gs = grad_rows[order].astype(jnp.float32)
    starts = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    seg = jnp.cumsum(starts) - 1                     # segment per sorted row
    summed = jax.ops.segment_sum(gs, seg, num_segments=n)   # (n, dim)
    # representative id per segment; padding segments -> num_rows (dropped)
    rep = jnp.full((n,), table.shape[0], sid.dtype).at[seg].min(sid)
    gnorm = jnp.sum(summed ** 2, axis=1)
    new_gsum = gsum[jnp.clip(rep, 0, table.shape[0] - 1)] + gnorm
    scale = lr / (jnp.sqrt(new_gsum) + 1e-10)
    table = table.at[rep].add(-(scale[:, None] * summed).astype(table.dtype),
                              mode="drop")
    gsum = gsum.at[rep].set(new_gsum, mode="drop")
    return table, gsum


class _TrainerBase:
    def __init__(self, model: GSgnnModel, task: str, out_dim: int = 1,
                 lr: float = 1e-3, rng=None,
                 sparse_embeds: Optional[Dict[str, SparseEmbedding]] = None,
                 evaluator=None, feature_store=None, device_sampler=None,
                 mesh=None, shard_gather: str = "alltoall",
                 remote_prefetch: int = 1, shard_dedup: bool = False,
                 shard_payload_dtype: str = "float32"):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(rng)
        self.model = model
        self.task = task
        self.params = {
            "gnn": init_gnn_model(k1, model),
            "dec": init_decoder(k2, task, model.hidden, out_dim,
                                num_etypes=len(model.etypes)),
        }
        self.optimizer = adamw(weight_decay=0.0)
        self.opt_state = self.optimizer.init(self.params)
        self.lr = lr
        self.stepno = jnp.zeros((), jnp.int32)
        self.sparse_embeds = sparse_embeds or {}
        self.feature_store = feature_store
        self.device_sampler = device_sampler
        self.evaluator = evaluator
        self.mesh = mesh
        if shard_gather not in ("alltoall", "gspmd"):
            raise ValueError(
                f"shard_gather must be 'alltoall' or 'gspmd', got "
                f"{shard_gather!r}")
        self.shard_gather = shard_gather
        self.remote_prefetch = int(remote_prefetch)
        if shard_payload_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"shard_payload_dtype must be 'float32' or 'bfloat16', got "
                f"{shard_payload_dtype!r}")
        self.shard_dedup = bool(shard_dedup)
        self.shard_payload_dtype = shard_payload_dtype
        if mesh is not None:
            self._place_on_mesh(mesh)
        self._steps: Dict = {}
        self.history: List[dict] = []

    # ------------------------------------------------------------------
    # data-parallel placement (docs/pipeline.md §"Data-parallel training"):
    # dense params/opt state/step counter are replicated over the mesh,
    # batches are sharded over the "data" axis, and any table the jitted
    # step reads must already live on the mesh (a buffer committed to a
    # lone device cannot be mixed with mesh-sharded step inputs).
    # ------------------------------------------------------------------
    def _place_on_mesh(self, mesh):
        from repro.common.sharding import replicate
        self.params = replicate(mesh, self.params)
        self.opt_state = replicate(mesh, self.opt_state)
        self.stepno = replicate(mesh, self.stepno)

        def on_mesh(x):
            return getattr(x.sharding, "mesh", None) == mesh

        for emb in self.sparse_embeds.values():
            if not on_mesh(emb.table):
                emb.table = replicate(mesh, emb.table)
                emb.gsum = replicate(mesh, emb.gsum)
        store = self.feature_store
        if store is not None:
            for nt, t in store.tables.items():
                if not on_mesh(t):
                    store.tables[nt] = replicate(mesh, t)
        if self.device_sampler is not None:
            for entry in self.device_sampler.tables.values():
                for k, t in entry.items():
                    if not on_mesh(t):
                        entry[k] = replicate(mesh, t)

    def _put_batch(self, x, batch_dim: int = 0):
        """Ship one host block to the device(s): sharded over the mesh's
        "data" axis when data-parallel, a plain transfer otherwise."""
        if self.mesh is None:
            return jnp.asarray(x)
        from repro.common.sharding import shard_batch
        return shard_batch(self.mesh, x, batch_dim)

    # ------------------------------------------------------------------
    def _feats_for(self, batch) -> Tuple[Dict, Dict, Dict]:
        """Compose input features: host-gathered raw feats + embedding-table
        rows for featureless ntypes + int32 index blocks for ntypes served
        by the device feature store. Returns (feats, emb_ids, gather_idx);
        the store gather itself happens inside the jitted step."""
        feats = dict(batch["arrays"]["feats"])
        emb_ids = {}
        gather_idx = {}
        store = self.feature_store
        expected = dict(self.model.feat_dims)
        for nt, ids in batch["input_nodes"].items():
            if nt in feats:
                continue
            if store is not None and nt in store:
                gather_idx[nt] = store.device_ids(ids)
            elif nt in self.sparse_embeds:
                feats[nt] = self.sparse_embeds[nt].lookup(ids)
                emb_ids[nt] = ids
            elif nt in expected:
                raise ValueError(
                    f"ntype {nt!r} has no feature source: the batch carries "
                    f"no host-gathered feats (loader host_features=False?) "
                    f"and the trainer has no feature_store/sparse_embeds "
                    f"entry for it — pass feature_store= (with matching "
                    f"feat_field) when loaders use host_features=False")
        return feats, emb_ids, gather_idx

    def _eval_feats(self, batch) -> Tuple[Dict, Dict]:
        """Eval-path features: store gathers run eagerly (still jitted)."""
        feats, emb_ids, gather_idx = self._feats_for(batch)
        if gather_idx:
            feats.update(self.feature_store.gather(gather_idx))
        return feats, emb_ids

    def _apply_sparse(self, emb_ids: Dict, feat_grads: Dict):
        for nt, ids in emb_ids.items():
            if nt in feat_grads:
                self.sparse_embeds[nt].apply_sparse_grad(ids, feat_grads[nt])

    def _adamw(self, grads, opt_state, params, stepno):
        """The dense update of one step: AdamW at the step's cosine
        learning rate."""
        with tracing.scope("adamw"):
            lr = cosine_schedule(stepno, 10, 10000, self.lr)
            return self.optimizer.update(grads, opt_state, params, stepno,
                                         lr)

    def _loss_and_out(self, params, feats, batch):
        raise NotImplementedError

    def _build_loss_fn(self, schema, roles=None, neg_shape=None, k=0,
                       head=None):
        """GNN apply + task head as one differentiable closure.  The
        default head is the trainer's ``_task_loss`` with the batch's
        static role metadata; the device step passes ``head=`` a
        ``TaskProgram.loss`` binding instead (same signature)."""
        if head is None:
            def head(params, emb, aux_in):
                return self._task_loss(params, emb, aux_in, roles=roles,
                                       neg_shape=neg_shape, k=k)

        def loss_fn(params, feats, arrays, aux_in, gather_idx, tables):
            arr = dict(arrays)
            # device-resident path: gather raw features from the resident
            # tables by the batch's int32 frontier indices, in-jit (fuses
            # with the input encoder; tables take no gradient)
            with tracing.scope("gather.features"):
                gathered = {nt: tables[nt][gather_idx[nt]]
                            for nt in gather_idx}
            arr["feats"] = {**gathered, **feats}
            emb = gnn_apply_blocks(params["gnn"], self.model, schema, arr)
            with tracing.scope("head"):
                return head(params, emb, aux_in)
        return loss_fn

    def _make_step(self, schema, roles=None, neg_shape=None, k=0):
        loss_fn = self._build_loss_fn(schema, roles=roles,
                                      neg_shape=neg_shape, k=k)

        def step(params, opt_state, stepno, feats, arrays, aux_in,
                 gather_idx, tables):
            (loss, out), (gp, gf) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, feats, arrays, aux_in, gather_idx, tables)
            params, opt_state = self._adamw(gp, opt_state, params, stepno)
            return params, opt_state, stepno + 1, loss, out, gf

        # donate params/opt_state/stepno: they are consumed and returned
        # updated, so XLA can alias the buffers (no per-step model copy)
        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _step_for(self, batch):
        key = (batch["schema"], batch.get("neg_shape"),
               tuple(batch.get("roles") or ()),
               batch.get("num_negatives", 0))
        if key not in self._steps:
            self._steps[key] = self._make_step(
                batch["schema"], roles=batch.get("roles"),
                neg_shape=batch.get("neg_shape"),
                k=batch.get("num_negatives", 0))
        return self._steps[key]

    # ------------------------------------------------------------------
    # device-resident sampling (feed mode 3, docs/pipeline.md): the whole
    # expand -> sample -> gather -> loss -> optimizer chain is one jitted
    # program; a batch ships only the task's int32 seed blocks (+ labels
    # and the padding mask).  Which blocks a batch carries, how they
    # concatenate into per-ntype GNN seeds (LP additionally draws its
    # negatives in-jit here), and the loss head are declared by the
    # task's TaskProgram (repro.trainer.task_programs); this engine owns
    # everything task-agnostic: sampling, gathers, AdamW + sparse
    # adagrad, lax.scan epochs, and both data-parallel lowerings.
    # ------------------------------------------------------------------
    def _device_program(self, batch_size: int):
        from repro.trainer.task_programs import program_for
        return program_for(self, batch_size)

    def _store_and_sparse_ntypes(self, plan):
        store = self.feature_store
        input_nts = [nt for nt, _ in plan.layers[0].src_counts]
        store_nts = tuple(nt for nt in input_nts
                          if store is not None and nt in store)
        sparse_nts = tuple(nt for nt in input_nts
                           if nt not in store_nts and nt in self.sparse_embeds)
        expected = dict(self.model.feat_dims)
        missing = [nt for nt in input_nts
                   if nt not in store_nts and nt not in sparse_nts
                   and nt in expected]
        if missing:
            raise ValueError(
                f"sample_on_device needs every featured ntype served "
                f"in-jit, but {missing} have no feature_store/"
                f"sparse_embeds entry — pass feature_store= (device "
                f"features) for raw-featured ntypes")
        return store_nts, sparse_nts

    def _check_plan_matches_program(self, plan, program):
        """The loader's plan and the trainer's program must agree on the
        seed layout, or the step would trace against the wrong shapes —
        e.g. a loader built with a different neg_method/num_negatives
        than the trainer's.  Fail with the mismatch spelled out."""
        want = program.seed_counts()
        got = dict(plan.seed_counts)
        if want != got:
            raise ValueError(
                f"the loader's sample plan ({got}) does not match the "
                f"trainer's task-program seed layout ({want}) — build "
                f"the loader with the trainer's task options (for LP: "
                f"the same neg_method / num_negatives)")

    def _make_device_step(self, schema, plan, batch_size):
        sampler = self.device_sampler
        store_nts, sparse_nts = self._store_and_sparse_ntypes(plan)
        if self.mesh is not None and self._dp_tables_replicated():
            return self._make_device_step_shard_map(plan, batch_size,
                                                    store_nts, sparse_nts)
        program = self._device_program(batch_size)
        self._check_plan_matches_program(plan, program)
        loss_fn = self._build_loss_fn(schema, head=program.loss)
        sparse_lrs = {nt: self.sparse_embeds[nt].lr for nt in sparse_nts}
        mesh = self.mesh
        # the donated sparse tables must come back with the sharding they
        # went in with (row-sharded or replicated), or XLA cannot alias
        # the buffers; capture the placement at trace-build time
        sparse_sh = {nt: (emb.table.sharding, emb.gsum.sharding)
                     for nt, emb in self.sparse_embeds.items()} \
            if mesh is not None else {}

        def step(params, opt_state, stepno, sparse_state, tables, csr,
                 blocks):
            with tracing.scope("expand"):
                seeds, aux_in, exclude = program.expand(blocks, stepno)
            with tracing.scope("sample"):
                masks, dts, frontier = sampler.sample(
                    csr, plan, seeds, stepno, exclude=exclude)
            arrays = {"masks": masks, "delta_t": dts}
            gather_idx = {nt: frontier[nt] for nt in store_nts}
            with tracing.scope("gather.embeddings"):
                feats = {nt: sparse_state[nt][0][frontier[nt]]
                         for nt in sparse_nts}
            # data-parallel note (GSPMD path): the blocks arrive sharded
            # over the "data" mesh axis; the loss is a *global* masked
            # mean, so the SPMD partitioner inserts the gradient
            # all-reduce and every shard applies the identical
            # replicated optimizer update
            (loss, out), (gp, gf) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, feats, arrays, aux_in, gather_idx, tables)
            params, opt_state = self._adamw(gp, opt_state, params, stepno)
            sparse_state = dict(sparse_state)
            with tracing.scope("sparse_adagrad"):
                for nt in sparse_nts:
                    sparse_state[nt] = _sparse_adagrad(
                        *sparse_state[nt], frontier[nt], gf[nt],
                        sparse_lrs[nt])
            if mesh is not None:
                from repro.common.sharding import constrain_replicated
                params = constrain_replicated(mesh, params)
                opt_state = constrain_replicated(mesh, opt_state)
                sparse_state = {
                    nt: tuple(jax.lax.with_sharding_constraint(a, sh)
                              for a, sh in zip(st, sparse_sh[nt]))
                    for nt, st in sparse_state.items()}
            return params, opt_state, stepno + 1, sparse_state, loss, out
        return step

    def _dp_tables_replicated(self) -> bool:
        """True when every table the device step reads is fully
        replicated on the mesh — the layout where each shard gathers
        locally and only gradients and the sparse scatter cross shards.
        Row-sharded tables (``shard_tables: true``) instead run the
        ragged all-to-all shard_map path (``shard_gather: alltoall``) or
        the legacy sharding-annotated-jit path (``gspmd``), where GSPMD
        lowers cross-shard gathers to collectives."""
        from jax.sharding import PartitionSpec as P
        leaves = []
        if self.feature_store is not None:
            leaves += list(self.feature_store.tables.values())
        for emb in self.sparse_embeds.values():
            leaves += [emb.table, emb.gsum]
        if self.device_sampler is not None:
            for entry in self.device_sampler.tables.values():
                leaves += list(entry.values())
        return all(getattr(x.sharding, "spec", None) == P()
                   for x in leaves)

    def _make_device_step_shard_map(self, plan, batch_size, store_nts,
                                    sparse_nts):
        """Data-parallel device step as an explicit shard_map: every
        shard runs the complete single-device program on its contiguous
        ``batch/n`` slice (drawing its rows of the *global* counter-based
        sample AND negative streams, so the union of shards reproduces
        the one-device draw bit-for-bit), and the shards meet at exactly
        the points the task program declares: the global masked-mean
        loss normalization, the gradient psum, the sparse-embedding
        scatter psum, and — for LP — the all-gathers of the dst
        embeddings (in-batch scores) and the SpotTarget pair list.  This
        is the GiGL/AGL minibatch-data-parallel layout — no resharding
        of the interleaved MFG frontier ever happens."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.gnn.schema import schema_of_plan
        from repro.trainer.task_programs import device_capability
        mesh = self.mesh
        n = int(mesh.shape["data"])
        sampler = self.device_sampler
        if batch_size % n != 0:
            raise ValueError(
                f"global batch {batch_size} is not divisible by the "
                f"{n}-way data mesh")
        missing = device_capability(
            self.task, neg_method=getattr(self, "neg_method", None),
            num_negatives=getattr(self, "num_negatives", 0),
            batch_size=batch_size, data_parallel=n)
        if missing:
            raise ValueError(f"sample_on_device: {missing}")
        program = self._device_program(batch_size // n)
        # every ntype's local seed rows must be an equal 1/n slice of
        # the loader's global plan, or the shard row maps are wrong
        got = dict(plan.seed_counts)
        for nt, c in program.seed_counts().items():
            if got.get(nt) != c * n:
                raise ValueError(
                    f"seed rows for ntype {nt!r} ({got.get(nt)}) are not "
                    f"{n} x the per-shard layout ({c}) — the loader's "
                    f"plan and the trainer's task program disagree")
        local_plan = sampler.plan_for(program.seed_counts())
        dp = ("data", n)
        loss_fn = self._build_loss_fn(
            schema_of_plan(local_plan),
            head=lambda p, e, a: program.loss(p, e, a, dp=dp))
        seed_maps = program.seed_maps(n)
        sparse_lrs = {nt: self.sparse_embeds[nt].lr for nt in sparse_nts}

        def local_step(params, opt_state, stepno, sparse_state, tables,
                       csr, blocks):
            with tracing.scope("expand"):
                seeds, aux_in, exclude = program.expand(blocks, stepno,
                                                        dp=dp)
            with tracing.scope("sample"):
                masks, dts, frontier = sampler.sample(
                    csr, local_plan, seeds, stepno, exclude=exclude,
                    dp=dp, seed_maps=seed_maps)
            arrays = {"masks": masks, "delta_t": dts}
            gather_idx = {nt: frontier[nt] for nt in store_nts}
            with tracing.scope("gather.embeddings"):
                feats = {nt: sparse_state[nt][0][frontier[nt]]
                         for nt in sparse_nts}

            def global_loss(p, f):
                # loss_fn yields the LOCAL masked mean; rescale so the
                # psum over shards is the GLOBAL masked mean
                # (sum_i num_i / sum_i den_i) — batch-size invariant
                loss, out = loss_fn(p, f, arrays, aux_in, gather_idx,
                                    tables)
                den = aux_in["mask"].sum().astype(jnp.float32)
                gden = jax.lax.psum(den, "data")
                return loss * den / jnp.maximum(gden, 1.0), out

            (loss, out), (gp, gf) = jax.value_and_grad(
                global_loss, argnums=(0, 1), has_aux=True)(params, feats)
            gp = jax.lax.psum(gp, "data")
            loss = jax.lax.psum(loss, "data")
            params, opt_state = self._adamw(gp, opt_state, params, stepno)
            sparse_state = dict(sparse_state)
            with tracing.scope("sparse_adagrad"):
                for nt in sparse_nts:
                    sparse_state[nt] = _sparse_adagrad_dp(
                        *sparse_state[nt], frontier[nt], gf[nt],
                        sparse_lrs[nt], "data")
            return params, opt_state, stepno + 1, sparse_state, loss, out

        repl = P()
        return shard_map(
            local_step, mesh=mesh,
            in_specs=(repl, repl, repl, repl, repl, repl, P("data")),
            out_specs=(repl, repl, repl, repl, repl, P("data")),
            check_vma=False)

    def _make_device_fns_alltoall(self, plan, batch_size, store_nts,
                                  sparse_nts, collect_stats: bool = False):
        """Data-parallel device step/epoch over *row-sharded* tables with
        explicit ragged all-to-all gathers (the ``shard_gather: alltoall``
        fast path).  Structure mirrors ``_make_device_step_shard_map`` —
        per-shard local programs on a ``batch/n`` slice of the global
        counter-based streams — but every table gather and the sparse
        scatter-back go through :class:`~repro.common.sharding
        .RaggedExchange`: shards ship only the rows others actually drew
        instead of letting GSPMD all-gather table slices.

        The step splits into two halves along the mutable-state boundary:

        - ``presample`` reads only *frozen* state (seed blocks, CSR,
          feature-store tables): task expand, the sharded draw (CSR row
          exchanges), the store-feature row exchange, and the *routing*
          (id exchange) for the sparse-embedding rows;
        - ``compute`` reads the mutable state (params, sparse tables):
          the sparse-row payload gather over the presampled routing, the
          differentiable loss, optimizer, and the gradient scatter-back
          through the same routing.

        With ``remote_prefetch > 0`` the epoch scan issues
        ``presample(k+1)`` before ``compute(k)`` each iteration — the two
        are dataflow-independent, so XLA overlaps batch k+1's row
        exchanges with batch k's model compute (remote rows double-buffer
        in the scan carry).  The sparse-adagrad scatter-back is pipelined
        one further stage behind (docs/pipeline.md §3e): batch k's
        gradient rows ride the carry and are scattered through batch k's
        *forward* routing at the top of iteration k+1, where the scatter
        is dataflow-independent of presample(k+2) and overlaps it instead
        of serializing at the tail of compute(k).  Semantics are
        unchanged in both pipeline stages: batch k+1's sparse payload
        gather still sees the tables with every update through batch k
        applied, so losses are bit-identical to the unpipelined step.

        Two more wire-level reductions ride the same exchanges:
        ``shard_dedup`` collapses duplicate row requests per shard with
        the static-capacity :func:`~repro.kernels.unique_rows
        .unique_rows` primitive before routing (overflow falls back to
        the plain exchange in-jit — always bit-identical), and
        ``shard_payload_dtype: bfloat16`` casts gathered float payloads
        to bf16 for the reduce-scatter wire format, restoring fp32 on
        arrival (exact per row — one owner per row means the psum never
        adds two nonzero bf16 values).
        """
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.common.sharding import (RaggedExchange, dedup_gather,
                                           unique_count, wire_row_bytes)
        from repro.gnn.schema import schema_of_plan
        from repro.trainer.task_programs import device_capability
        mesh = self.mesh
        n = int(mesh.shape["data"])
        sampler = self.device_sampler
        if batch_size % n != 0:
            raise ValueError(
                f"global batch {batch_size} is not divisible by the "
                f"{n}-way data mesh")
        missing = device_capability(
            self.task, neg_method=getattr(self, "neg_method", None),
            num_negatives=getattr(self, "num_negatives", 0),
            batch_size=batch_size, data_parallel=n)
        if missing:
            raise ValueError(f"sample_on_device: {missing}")
        program = self._device_program(batch_size // n)
        got = dict(plan.seed_counts)
        for nt, c in program.seed_counts().items():
            if got.get(nt) != c * n:
                raise ValueError(
                    f"seed rows for ntype {nt!r} ({got.get(nt)}) are not "
                    f"{n} x the per-shard layout ({c}) — the loader's "
                    f"plan and the trainer's task program disagree")
        local_plan = sampler.plan_for(program.seed_counts())
        dp = ("data", n)
        loss_fn = self._build_loss_fn(
            schema_of_plan(local_plan),
            head=lambda p, e, a: program.loss(p, e, a, dp=dp))
        seed_maps = program.seed_maps(n)
        sparse_lrs = {nt: self.sparse_embeds[nt].lr for nt in sparse_nts}

        def spec_of(x):
            s = getattr(x.sharding, "spec", None)
            return s if s is not None else P()

        store_tables = (self.feature_store.tables
                        if self.feature_store is not None else {})
        # mixed layouts are legal: a table whose rows did not shard (or
        # was placed replicated) keeps the plain local gather
        store_sh = {nt: spec_of(store_tables[nt]) != P() for nt in store_nts}
        store_dt = {nt: store_tables[nt].dtype for nt in store_nts}
        sparse_sh = {nt: spec_of(self.sparse_embeds[nt].table) != P()
                     for nt in sparse_nts}
        # per-shard row block of each sharded sparse table, captured at
        # build time (presample never sees the mutable table itself)
        sparse_rps = {nt: self.sparse_embeds[nt].table.shape[0] // n
                      for nt in sparse_nts if sparse_sh[nt]}
        csr_sh = [spec_of(e["col_idx"]) != P()
                  for e in sampler.tables.values()]
        if any(csr_sh) and not all(csr_sh):
            raise ValueError(
                "mixed sharded/replicated CSR tables in one sampler are "
                "not supported by the alltoall gather path")
        shard_arg = dp if csr_sh and all(csr_sh) else None
        wire_dt = (jnp.bfloat16 if self.shard_payload_dtype == "bfloat16"
                   else None)
        dedup = self.shard_dedup
        # wire bytes of one sparse-embedding row, for the stats probe
        # (presample routes but never touches the mutable table itself)
        sparse_pb = {nt: wire_row_bytes(self.sparse_embeds[nt].table,
                                        wire_dt)
                     for nt in sparse_nts if sparse_sh[nt]}

        def wire_tables(tables):
            # The feature store is frozen for the duration of an epoch
            # dispatch, so the cast to the wire dtype can happen once
            # here instead of inside every per-batch gather: the scan
            # body's takes/masks then move 2-byte rows throughout.  The
            # exchange results are widened back at the presample call
            # sites, so downstream compute sees the exact values the
            # per-gather cast produced (cast commutes with take/mask).
            if wire_dt is None:
                return tables
            return {nt: (t.astype(wire_dt)
                         if store_sh.get(nt, False)
                         and jnp.issubdtype(t.dtype, jnp.floating)
                         else t)
                    for nt, t in tables.items()}

        def presample(tables, csr, blocks, stepno):
            sink = [] if collect_stats else None
            with tracing.scope("expand"):
                seeds, aux_in, exclude = program.expand(blocks, stepno,
                                                        dp=dp)
            with tracing.scope("sample"):
                masks, dts, frontier = sampler.sample(
                    csr, local_plan, seeds, stepno, exclude=exclude,
                    dp=dp, seed_maps=seed_maps, shard=shard_arg,
                    shard_dedup=dedup, stats_sink=sink)
            with tracing.scope("gather.features"):
                store_feats = {}
                for nt in store_nts:
                    if store_sh[nt] and dedup:
                        store_feats[nt] = dedup_gather(
                            frontier[nt], tables[nt], axis_name="data",
                            n_shards=n, rows_per_shard=tables[nt].shape[0],
                            wire_dtype=wire_dt,
                            stats_sink=sink).astype(store_dt[nt])
                    elif store_sh[nt]:
                        if sink is not None:
                            sink.append({
                                "requests": frontier[nt].shape[0],
                                "distinct": unique_count(frontier[nt]),
                                "capacity": frontier[nt].shape[0],
                                "payload_bytes": wire_row_bytes(tables[nt],
                                                                wire_dt),
                                "fits": jnp.int32(1)})
                        ex = RaggedExchange(
                            frontier[nt], axis_name="data", n_shards=n,
                            rows_per_shard=tables[nt].shape[0])
                        store_feats[nt] = ex.gather(
                            tables[nt],
                            wire_dtype=wire_dt).astype(store_dt[nt])
                    else:
                        store_feats[nt] = tables[nt][frontier[nt]]
            # sparse routings stay un-deduplicated: the exchange must be
            # reusable for the backward scatter (duplicate grad rows sum
            # through the routing) and ride the scan carry with a static
            # shape — dedup's overflow cond cannot change the carry.
            with tracing.scope("gather.embeddings"):
                sparse_route = {
                    nt: RaggedExchange(frontier[nt], axis_name="data",
                                       n_shards=n,
                                       rows_per_shard=sparse_rps[nt])
                    for nt in sparse_nts if sparse_sh[nt]}
            if sink is not None:
                for nt in sparse_nts:
                    if sparse_sh[nt]:
                        sink.append({
                            "requests": frontier[nt].shape[0],
                            "distinct": unique_count(frontier[nt]),
                            "capacity": frontier[nt].shape[0],
                            "payload_bytes": sparse_pb[nt],
                            "fits": jnp.int32(1)})
            sparse_ids = {nt: frontier[nt] for nt in sparse_nts
                          if not sparse_sh[nt]}
            pf = {"masks": masks, "dts": dts, "aux_in": aux_in,
                  "store_feats": store_feats,
                  "sparse_route": sparse_route,
                  "sparse_ids": sparse_ids}
            if collect_stats:
                pf["exg"] = sink
            return pf

        def compute_fwd(params, opt_state, stepno, sparse_state, pf):
            """Forward + dense update: everything in ``compute`` except
            the sparse-adagrad scatter-back, whose gradient rows are
            returned instead (for the pipelined ``apply_sparse``)."""
            arrays = {"masks": pf["masks"], "delta_t": pf["dts"]}
            aux_in = pf["aux_in"]
            feats = dict(pf["store_feats"])
            with tracing.scope("gather.embeddings"):
                for nt in sparse_nts:
                    feats[nt] = (
                        pf["sparse_route"][nt].gather(sparse_state[nt][0],
                                                      wire_dtype=wire_dt)
                        if sparse_sh[nt]
                        else sparse_state[nt][0][pf["sparse_ids"][nt]])

            def global_loss(p, f):
                # loss_fn yields the LOCAL masked mean; rescale so the
                # psum over shards is the GLOBAL masked mean
                loss, out = loss_fn(p, f, arrays, aux_in, {}, {})
                den = aux_in["mask"].sum().astype(jnp.float32)
                gden = jax.lax.psum(den, "data")
                return loss * den / jnp.maximum(gden, 1.0), out

            (loss, out), (gp, gf) = jax.value_and_grad(
                global_loss, argnums=(0, 1), has_aux=True)(params, feats)
            gp = jax.lax.psum(gp, "data")
            loss = jax.lax.psum(loss, "data")
            params, opt_state = self._adamw(gp, opt_state, params, stepno)
            gf_sp = {nt: gf[nt] for nt in sparse_nts}
            return params, opt_state, stepno + 1, loss, out, gf_sp

        def apply_sparse(sparse_state, routes, ids, gf_sp):
            """Sparse-adagrad scatter-back of one batch's gradient rows
            through that batch's forward routing.  Gradient rows of all
            zeros are an exact no-op (summed grad 0 -> gsum and table
            unchanged), which makes the pipeline's zero-initialised
            pending stage safe to apply."""
            sparse_state = dict(sparse_state)
            with tracing.scope("sparse_adagrad"):
                for nt in sparse_nts:
                    if sparse_sh[nt]:
                        sparse_state[nt] = _sparse_adagrad_shard(
                            *sparse_state[nt], routes[nt], gf_sp[nt],
                            sparse_lrs[nt])
                    else:
                        sparse_state[nt] = _sparse_adagrad_dp(
                            *sparse_state[nt], ids[nt], gf_sp[nt],
                            sparse_lrs[nt], "data")
            return sparse_state

        def compute(params, opt_state, stepno, sparse_state, pf):
            params, opt_state, stepno, loss, out, gf_sp = compute_fwd(
                params, opt_state, stepno, sparse_state, pf)
            sparse_state = apply_sparse(sparse_state, pf["sparse_route"],
                                        pf["sparse_ids"], gf_sp)
            return params, opt_state, stepno, sparse_state, loss, out

        def local_step(params, opt_state, stepno, sparse_state, tables,
                       csr, blocks):
            pf = presample(tables, csr, blocks, stepno)
            return compute(params, opt_state, stepno, sparse_state, pf)

        if self.remote_prefetch > 0:
            # zero "pending" gradient rows for the pipelined scatter-back
            # (shapes are static per batch: frontier rows x embed dim)
            def zero_pending(pf0):
                z = {}
                for nt in sparse_nts:
                    rows = (pf0["sparse_route"][nt].n_requests
                            if sparse_sh[nt]
                            else pf0["sparse_ids"][nt].shape[0])
                    tbl = self.sparse_embeds[nt].table
                    z[nt] = jnp.zeros((rows,) + tbl.shape[1:], tbl.dtype)
                return z

            def local_epoch(params, opt_state, stepno, sparse_state,
                            tables, csr, blocks):
                tm = jax.tree_util.tree_map
                # one cast per epoch dispatch; the scan body closes over
                # the narrow tables as a loop constant
                tables = wire_tables(tables)
                pf0 = presample(tables, csr, tm(lambda v: v[0], blocks),
                                stepno)
                # xs[k] = blocks[k+1]: each iteration presamples the NEXT
                # batch before computing the current one (the wrap-around
                # presample of blocks[0] is discarded — static shapes)
                shifted = tm(lambda v: jnp.roll(v, -1, axis=0), blocks)
                pending0 = (pf0["sparse_route"], pf0["sparse_ids"],
                            zero_pending(pf0))

                # pipeline: batch k-1's scatter-back applies at the top
                # of iteration k, overlapping presample(k+1) (which reads
                # no mutable state); compute_fwd(k) then sees every
                # update through batch k-1 — the same tables the
                # unpipelined schedule would hand it.
                def body(carry, xs):
                    p, o, s, sp, pf, pending = carry
                    sp = apply_sparse(sp, *pending)
                    pf_next = presample(tables, csr, xs, s + 1)
                    p, o, s, loss, _, gf_sp = compute_fwd(p, o, s, sp, pf)
                    pending = (pf["sparse_route"], pf["sparse_ids"],
                               gf_sp)
                    return (p, o, s, sp, pf_next, pending), loss
                (params, opt_state, stepno, sparse_state, _, pending), \
                    losses = jax.lax.scan(
                        body,
                        (params, opt_state, stepno, sparse_state, pf0,
                         pending0),
                        shifted)
                # flush the last batch's scatter-back
                sparse_state = apply_sparse(sparse_state, *pending)
                return params, opt_state, stepno, sparse_state, losses
        else:
            base_epoch = self._make_device_epoch(local_step)

            def local_epoch(params, opt_state, stepno, sparse_state,
                            tables, csr, blocks):
                return base_epoch(params, opt_state, stepno, sparse_state,
                                  wire_tables(tables), csr, blocks)

        repl = P()
        sparse_specs = {nt: (spec_of(emb.table), spec_of(emb.gsum))
                        for nt, emb in self.sparse_embeds.items()}
        table_specs = {nt: spec_of(t) for nt, t in store_tables.items()}
        csr_specs = {et: {k: spec_of(t) for k, t in entry.items()}
                     for et, entry in sampler.tables.items()}
        common = (repl, repl, repl, sparse_specs, table_specs, csr_specs)
        step_sm = shard_map(
            local_step, mesh=mesh, in_specs=common + (P("data"),),
            out_specs=(repl, repl, repl, sparse_specs, repl, P("data")),
            check_vma=False)
        epoch_sm = shard_map(
            local_epoch, mesh=mesh, in_specs=common + (P(None, "data"),),
            out_specs=(repl, repl, repl, sparse_specs, repl),
            check_vma=False)
        probe_sm = None
        if collect_stats:
            # measured-exchange probe: run one presample and return every
            # exchange site's {requests, distinct, capacity,
            # payload_bytes, fits} as (n_shards,) columns
            def probe(tables, csr, blocks, stepno):
                pf = presample(tables, csr, blocks, stepno)
                return [{k: jnp.asarray(v, jnp.int32).reshape(1)
                         for k, v in e.items()} for e in pf["exg"]]
            probe_sm = shard_map(
                probe, mesh=mesh,
                in_specs=(table_specs, csr_specs, P("data"), repl),
                out_specs=P("data"), check_vma=False)
        return step_sm, epoch_sm, probe_sm

    @staticmethod
    def _make_device_epoch(step):
        """lax.scan the device step over a stacked epoch of seed-block
        batches: one dispatch, zero host round-trips between
        minibatches.  ``blocks`` is the task program's dict of stacked
        ``(num_batches, ...)`` arrays (scan carries the pytree)."""
        def epoch(params, opt_state, stepno, sparse_state, tables, csr,
                  blocks):
            def body(carry, xs):
                p, o, s, sp = carry
                p, o, s, sp, loss, _ = step(p, o, s, sp, tables, csr, xs)
                return (p, o, s, sp), loss
            (params, opt_state, stepno, sparse_state), losses = jax.lax.scan(
                body, (params, opt_state, stepno, sparse_state), blocks)
            return params, opt_state, stepno, sparse_state, losses
        return epoch

    def _check_device_sampler(self, sampler):
        """The jitted step draws with the *trainer's* sampler; a loader
        built around a different one would silently train on a different
        sample stream — fail loudly instead."""
        if self.device_sampler is None:
            raise ValueError(
                "sample_on_device needs the trainer built with "
                "device_sampler= (the same DeviceNeighborSampler as the "
                "loader)")
        if sampler is not None and sampler is not self.device_sampler:
            raise ValueError(
                "the loader's DeviceNeighborSampler is not the trainer's "
                "device_sampler — the step draws with the trainer's, so "
                "the loader's seed/tables would be silently ignored; "
                "build the loader with sampler=trainer.device_sampler")

    def _device_fns_for(self, schema, plan, batch_size):
        key = ("device", schema)
        if key not in self._steps:
            if (self.mesh is not None and self.shard_gather == "alltoall"
                    and not self._dp_tables_replicated()):
                store_nts, sparse_nts = self._store_and_sparse_ntypes(plan)
                raw_step, raw_epoch, _ = self._make_device_fns_alltoall(
                    plan, batch_size, store_nts, sparse_nts)
            else:
                raw_step = self._make_device_step(schema, plan, batch_size)
                raw_epoch = self._make_device_epoch(raw_step)
            self._steps[key] = {
                "step": jax.jit(raw_step, donate_argnums=(0, 1, 2, 3)),
                "epoch": jax.jit(raw_epoch, donate_argnums=(0, 1, 2, 3)),
            }
        return self._steps[key]

    # ------------------------------------------------------------------
    # streaming epoch engine (docs/pipeline.md §3f): host-sampled feed
    # modes 1-2 lower through the SAME scanned-epoch machinery as the
    # device path — the loader stacks a whole epoch of sampled blocks
    # into one numpy pytree (``epoch_blocks``) and the step below runs
    # the per-batch host program (gather -> GNN -> loss -> AdamW +
    # sparse adagrad) inside the shared ``_make_device_epoch`` scan,
    # with the same donation and the same data-parallel lowerings.
    # ------------------------------------------------------------------
    def _host_ntype_split(self, idx_nts):
        """Partition the stacked epoch's int32 index blocks (ntypes the
        loader gathered no host features for) into device-store gathers
        vs in-carry sparse-embedding rows — the host-path analogue of
        ``_store_and_sparse_ntypes``."""
        store = self.feature_store
        store_nts, sparse_nts = [], []
        expected = dict(self.model.feat_dims)
        for nt in idx_nts:
            if store is not None and nt in store:
                store_nts.append(nt)
            elif nt in self.sparse_embeds:
                sparse_nts.append(nt)
            elif nt in expected:
                raise ValueError(
                    f"ntype {nt!r} has no feature source for the "
                    f"streaming host engine: the loader gathered no host "
                    f"feats for it (host_features=False?) and the trainer "
                    f"has no feature_store/sparse_embeds entry — pass "
                    f"feature_store= (with matching feat_field)")
        return tuple(store_nts), tuple(sparse_nts)

    def _make_host_step(self, schema, roles, neg_shape, k, store_nts,
                        sparse_nts):
        """One host-sampled batch as a scan-able step with the device
        step's signature (``csr`` is a dummy — sampling already happened
        on the host).  With a mesh this is also the GSPMD data-parallel
        lowering: the program stays global and the partitioner shards
        it along the batch-sharded inputs."""
        loss_fn = self._build_loss_fn(schema, roles=roles,
                                      neg_shape=neg_shape, k=k)
        sparse_lrs = {nt: self.sparse_embeds[nt].lr for nt in sparse_nts}
        mesh = self.mesh
        sparse_sh = {nt: (emb.table.sharding, emb.gsum.sharding)
                     for nt, emb in self.sparse_embeds.items()} \
            if mesh is not None else {}

        def step(params, opt_state, stepno, sparse_state, tables, csr, xs):
            del csr
            arrays = {"masks": xs["masks"], "delta_t": xs["delta_t"]}
            gather_idx = {nt: xs["idx"][nt] for nt in store_nts}
            feats = dict(xs["feats"])
            with tracing.scope("gather.embeddings"):
                for nt in sparse_nts:
                    feats[nt] = sparse_state[nt][0][xs["idx"][nt]]
            (loss, out), (gp, gf) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, feats, arrays, xs["aux"], gather_idx, tables)
            params, opt_state = self._adamw(gp, opt_state, params, stepno)
            sparse_state = dict(sparse_state)
            with tracing.scope("sparse_adagrad"):
                for nt in sparse_nts:
                    sparse_state[nt] = _sparse_adagrad(
                        *sparse_state[nt], xs["idx"][nt], gf[nt],
                        sparse_lrs[nt])
            if mesh is not None:
                from repro.common.sharding import constrain_replicated
                params = constrain_replicated(mesh, params)
                opt_state = constrain_replicated(mesh, opt_state)
                sparse_state = {
                    nt: tuple(jax.lax.with_sharding_constraint(a, sh)
                              for a, sh in zip(st, sparse_sh[nt]))
                    for nt, st in sparse_state.items()}
            return params, opt_state, stepno + 1, sparse_state, loss, out
        return step

    def _make_host_fns_shard_map(self, loader, xs, store_nts, sparse_nts):
        """Host-sampled data-parallel epoch as an explicit shard_map
        (mesh + replicated tables — mirrors the device path's
        ``_make_device_step_shard_map``).  The loader samples the
        GLOBAL batch once (dp1-identical draws); a host-side ``prepare``
        pass then permutes every frontier-indexed row block shard-major
        (``shard_host_perms`` — the numpy mirror of the device path's
        affine seed maps), so a contiguous ``P(None, "data")`` slice of
        each array IS one shard's local MFG in local-plan row order, and
        every shard runs the complete local program on its slice.
        Shards meet only at the global masked-mean rescale, the gradient
        psum, and the sparse-embedding scatter psum."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.sampling import plan_sample, shard_host_perms
        from repro.gnn.schema import ekey, schema_of_plan
        from repro.trainer.task_programs import role_layout
        mesh = self.mesh
        n = int(mesh.shape["data"])
        if self.task == "link_prediction":
            raise ValueError(
                "host-sampled link prediction cannot lower through the "
                "shard_map data-parallel engine (shared/in-batch negative "
                "scoring reads other shards' dst embeddings) — use a "
                "sample_on_device loader for data-parallel LP, or "
                "data_parallel: 1")
        B = int(loader.batch_size)
        roles = loader.roles
        global_rl = ([(nt, ln) for nt, _, ln in roles] if roles is not None
                     else [(self.target_ntype, B)])
        if any(ln % n for _, ln in global_rl):
            raise ValueError(
                f"every seed role must be divisible by the {n}-way data "
                f"mesh, got {global_rl}")
        local_rl = [(nt, ln // n) for nt, ln in global_rl]
        local_counts, local_roles = role_layout(local_rl)
        local_plan = plan_sample(loader.graph, loader.fanout, local_counts)
        local_schema = schema_of_plan(local_plan)
        dst_perms, input_perms = shard_host_perms(local_plan, local_rl, n)
        loss_fn = self._build_loss_fn(
            local_schema, roles=(local_roles if roles is not None else None))
        sparse_lrs = {nt: self.sparse_embeds[nt].lr for nt in sparse_nts}

        def local_step(params, opt_state, stepno, sparse_state, tables,
                       csr, xsb):
            del csr
            arrays = {"masks": xsb["masks"], "delta_t": xsb["delta_t"]}
            gather_idx = {nt: xsb["idx"][nt] for nt in store_nts}
            feats = dict(xsb["feats"])
            with tracing.scope("gather.embeddings"):
                for nt in sparse_nts:
                    feats[nt] = sparse_state[nt][0][xsb["idx"][nt]]
            aux_in = xsb["aux"]

            def global_loss(p, f):
                # loss_fn yields the LOCAL masked mean; rescale so the
                # psum over shards is the GLOBAL masked mean
                loss, out = loss_fn(p, f, arrays, aux_in, gather_idx,
                                    tables)
                den = aux_in["mask"].sum().astype(jnp.float32)
                gden = jax.lax.psum(den, "data")
                return loss * den / jnp.maximum(gden, 1.0), out

            (loss, out), (gp, gf) = jax.value_and_grad(
                global_loss, argnums=(0, 1), has_aux=True)(params, feats)
            gp = jax.lax.psum(gp, "data")
            loss = jax.lax.psum(loss, "data")
            params, opt_state = self._adamw(gp, opt_state, params, stepno)
            sparse_state = dict(sparse_state)
            with tracing.scope("sparse_adagrad"):
                for nt in sparse_nts:
                    sparse_state[nt] = _sparse_adagrad_dp(
                        *sparse_state[nt], xsb["idx"][nt], gf[nt],
                        sparse_lrs[nt], "data")
            return params, opt_state, stepno + 1, sparse_state, loss, out

        local_epoch = self._make_device_epoch(local_step)
        repl = P()
        xs_spec = jax.tree_util.tree_map(lambda _: P(None, "data"), xs)
        epoch_sm = shard_map(
            local_epoch, mesh=mesh,
            in_specs=(repl, repl, repl, repl, repl, repl, xs_spec),
            out_specs=(repl, repl, repl, repl, repl),
            check_vma=False)

        # which ntype's frontier rows each etype's mask/Δt block indexes
        layer_dst = [{ekey(pe.etype): pe.etype[2] for pe in pl.edges}
                     for pl in local_plan.layers]

        def prepare(xs_np):
            out = dict(xs_np)
            out["feats"] = {nt: v[:, input_perms[nt]]
                            for nt, v in xs_np["feats"].items()}
            out["idx"] = {nt: v[:, input_perms[nt]]
                          for nt, v in xs_np["idx"].items()}
            out["masks"] = [
                {ek: v[:, dst_perms[li][layer_dst[li][ek]]]
                 for ek, v in layer.items()}
                for li, layer in enumerate(xs_np["masks"])]
            out["delta_t"] = [
                {ek: v[:, dst_perms[li][layer_dst[li][ek]]]
                 for ek, v in layer.items()}
                for li, layer in enumerate(xs_np["delta_t"])]
            return out
        return epoch_sm, prepare

    def _host_put(self, tree):
        return jax.tree_util.tree_map(lambda v: self._put_batch(v, 1), tree)

    def _host_fns_for(self, loader, xs):
        key = ("host", loader.schema, tuple(loader.roles or ()),
               loader.neg_shape, loader.num_negatives)
        if key not in self._steps:
            store_nts, sparse_nts = self._host_ntype_split(sorted(xs["idx"]))
            if self.mesh is not None and self._dp_tables_replicated():
                raw_epoch, prepare = self._make_host_fns_shard_map(
                    loader, xs, store_nts, sparse_nts)
            else:
                step = self._make_host_step(
                    loader.schema, loader.roles, loader.neg_shape,
                    loader.num_negatives, store_nts, sparse_nts)
                raw_epoch = self._make_device_epoch(step)
                prepare = None
            self._steps[key] = {
                "epoch": jax.jit(raw_epoch, donate_argnums=(0, 1, 2, 3)),
                "prepare": prepare, "put": self._host_put}
        return self._steps[key]

    def _engine_fns_for(self, loader, xs):
        """Streaming-engine entry point: one scanned (chunkable) epoch
        program for whichever feed mode the loader speaks, plus the
        host-side ``prepare`` (shard-major permutation, when the dp
        lowering needs one) and ``put`` (device placement) closures."""
        if getattr(loader, "sample_on_device", False):
            self._check_device_sampler(getattr(loader, "sampler", None))
            fns = self._device_fns_for(loader.schema, loader.plan,
                                       loader.batch_size)
            return {"epoch": fns["epoch"], "prepare": None,
                    "put": lambda blocks: {k: self._put_batch(v, 1)
                                           for k, v in blocks.items()}}
        return self._host_fns_for(loader, xs)

    # ------------------------------------------------------------------
    # device-resident validation (``eval_on_device``): a jitted scan
    # over the staged validation epoch accumulates the evaluator's
    # (num, den) state in-jit — the host fetches two scalars per epoch
    # instead of running the per-batch ``evaluate`` loop.  Same metric
    # contract as the host evaluators (``device_update``/``merge``).
    # ------------------------------------------------------------------
    def _eval_update(self):
        """jit-traceable fold of one batch's outputs into the (num, den)
        metric carry — mirrors ``evaluator.update`` on the host."""
        upd = self.evaluator.device_update()

        def apply(carry, out, aux_in):
            num, den = carry
            return upd(num, den, out, aux_in["labels"], aux_in["mask"])
        return apply

    def _make_eval_device(self, schema, plan, batch_size):
        """Eval pass over a device-sampled loader's stacked seed blocks:
        draws use a dedicated step range (``_EVAL_STEP_BASE + i``) of
        the counter-based stream, so validation subgraphs are
        deterministic per batch index and never collide with training
        steps."""
        program = self._device_program(batch_size)
        self._check_plan_matches_program(plan, program)
        sampler = self.device_sampler
        store_nts, sparse_nts = self._store_and_sparse_ntypes(plan)
        loss_fn = self._build_loss_fn(schema, head=program.loss)
        upd = self._eval_update()

        def eval_epoch(params, sparse_state, tables, csr, blocks):
            nb = jax.tree_util.tree_leaves(blocks)[0].shape[0]
            steps = _EVAL_STEP_BASE + jnp.arange(nb, dtype=jnp.int32)

            def body(carry, xsb):
                blk, step = xsb
                with tracing.scope("expand"):
                    seeds, aux_in, exclude = program.expand(blk, step)
                with tracing.scope("sample"):
                    masks, dts, frontier = sampler.sample(
                        csr, plan, seeds, step, exclude=exclude)
                arrays = {"masks": masks, "delta_t": dts}
                gather_idx = {nt: frontier[nt] for nt in store_nts}
                feats = {nt: sparse_state[nt][0][frontier[nt]]
                         for nt in sparse_nts}
                _, out = loss_fn(params, feats, arrays, aux_in,
                                 gather_idx, tables)
                return upd(carry, out, aux_in), None

            z = jnp.zeros((), jnp.float32)
            (num, den), _ = jax.lax.scan(body, (z, z), (blocks, steps))
            return num, den
        return eval_epoch

    def _make_eval_host(self, schema, roles, neg_shape, k, store_nts,
                        sparse_nts):
        loss_fn = self._build_loss_fn(schema, roles=roles,
                                      neg_shape=neg_shape, k=k)
        upd = self._eval_update()

        def eval_epoch(params, sparse_state, tables, csr, xs):
            del csr

            def body(carry, xsb):
                arrays = {"masks": xsb["masks"], "delta_t": xsb["delta_t"]}
                gather_idx = {nt: xsb["idx"][nt] for nt in store_nts}
                feats = dict(xsb["feats"])
                for nt in sparse_nts:
                    feats[nt] = sparse_state[nt][0][xsb["idx"][nt]]
                _, out = loss_fn(params, feats, arrays, xsb["aux"],
                                 gather_idx, tables)
                return upd(carry, out, xsb["aux"]), None

            z = jnp.zeros((), jnp.float32)
            (num, den), _ = jax.lax.scan(body, (z, z), xs)
            return num, den
        return eval_epoch

    def _eval_fns_for(self, loader, xs):
        if self.evaluator is None:
            raise ValueError("eval_on_device needs the trainer built "
                             "with an evaluator")
        if self.mesh is not None and not self._dp_tables_replicated():
            raise ValueError(
                "eval_on_device is not supported with row-sharded tables "
                "(shard_tables: true) — run host evaluation instead "
                "(eval_on_device: false)")
        if getattr(loader, "sample_on_device", False):
            key = ("eval_device", loader.schema)
            if key not in self._steps:
                raw = self._make_eval_device(loader.schema, loader.plan,
                                             loader.batch_size)
                self._steps[key] = {
                    "epoch": jax.jit(raw),
                    "put": lambda blocks: {k: self._put_batch(v, 1)
                                           for k, v in blocks.items()}}
            return self._steps[key]
        key = ("eval_host", loader.schema, tuple(loader.roles or ()),
               loader.neg_shape, loader.num_negatives)
        if key not in self._steps:
            store_nts, sparse_nts = self._host_ntype_split(sorted(xs["idx"]))
            raw = self._make_eval_host(loader.schema, loader.roles,
                                       loader.neg_shape,
                                       loader.num_negatives,
                                       store_nts, sparse_nts)
            self._steps[key] = {"epoch": jax.jit(raw),
                                "put": self._host_put}
        return self._steps[key]

    def _snapshot_fn(self):
        """Jitted device copy of the (params, opt_state, stepno, sparse)
        carry: dispatched by the engine before the next epoch's donation
        can recycle the live buffers, so async checkpoint writers read a
        stable snapshot."""
        key = ("snapshot",)
        if key not in self._steps:
            self._steps[key] = jax.jit(
                lambda c: jax.tree_util.tree_map(jnp.copy, c))
        return self._steps[key]

    # ------------------------------------------------------------------
    # inference-only device program (serving / offline reference): the
    # same sample -> gather -> GNN chain as the device step, but ending
    # at the task's serve head — no loss, no optimizer, params untouched
    # ------------------------------------------------------------------
    def device_infer_program(self, batch_size: int) -> "DeviceInferProgram":
        key = ("infer", int(batch_size))
        if key not in self._steps:
            self._steps[key] = DeviceInferProgram(self, batch_size)
        return self._steps[key]

    def infer_device(self, seeds, batch_size: Optional[int] = None,
                     step: int = 0):
        """Offline reference inference on the device engine: pad ``seeds``
        to ``batch_size`` (default: their own length) and run the
        inference-only program once at ``step``.  Returns host arrays
        ``{"emb": (n, hidden), "out": (n, ...)}``.

        This is the serving parity anchor: the program's draws are
        seed-keyed (``sample(seed_keyed=True)``), so each returned row
        is a pure function of its seed's node id — bit-identical to the
        same seed served in any batch, at any position, at any step, by
        any replica."""
        ids = np.asarray(seeds, np.int64).reshape(-1)
        from repro.core.sampling import pad_seeds
        padded, _ = pad_seeds(ids, int(batch_size or len(ids)))
        prog = self.device_infer_program(len(padded))
        emb, out = prog(padded, step)
        n = len(ids)
        return {"emb": np.asarray(emb)[:n], "out": np.asarray(out)[:n]}

    def _sparse_pack(self):
        return {nt: (emb.table, emb.gsum)
                for nt, emb in self.sparse_embeds.items()}

    def _sparse_unpack(self, state):
        for nt, (table, gsum) in state.items():
            self.sparse_embeds[nt].table = table
            self.sparse_embeds[nt].gsum = gsum

    def _fit_batch_device(self, batch):
        self._check_device_sampler(batch.get("sampler"))
        fns = self._device_fns_for(batch["schema"], batch["plan"],
                                   batch["batch_size"])
        tables = (self.feature_store.tables
                  if self.feature_store is not None else {})
        state = self._sparse_pack()
        blocks = {k: self._put_batch(v) for k, v in batch["blocks"].items()}
        self.params, self.opt_state, self.stepno, state, loss, out = \
            fns["step"](self.params, self.opt_state, self.stepno, state,
                        tables, self.device_sampler.tables, blocks)
        self._sparse_unpack(state)
        return float(loss), out

    def exchange_report(self, loader):
        """Measured wire traffic of one sharded-table training batch on
        the ``shard_gather: alltoall`` path (benchmarks/bench_scaling.py
        derives its ``exchanged_bytes_step`` / ``dedup_ratio`` columns
        from this — docs/pipeline.md §3e).

        Runs the presample half of the step (all routing, no mutable
        state) over the loader's first batch with per-exchange-site stats
        collection on, and aggregates over sites and shards.  Byte
        accounting per site: every shard ships its ``(n_shards, slots)``
        id buffer (all_gather, 4 B/slot) and its ``(n_shards, slots,
        row)`` payload buffer (psum_scatter, wire-dtype row bytes), so a
        site costs ``n_shards^2 * slots * (4 + payload_bytes)`` — with
        ``slots`` the dedup capacity when every shard's distinct count
        fits, else the raw request count (the in-jit fallback's wire
        format; the single count slot the dedup id wire appends is
        noise and ignored).  ``dedup_ratio`` is distinct/requested rows summed over
        sites and shards (< 1.0 whenever any frontier repeats a row).
        """
        if (self.mesh is None or self.shard_gather != "alltoall"
                or self._dp_tables_replicated()):
            raise ValueError(
                "exchange_report needs the sharded-table alltoall path "
                "(mesh= trainer with row-sharded tables and "
                "shard_gather='alltoall')")
        batch = next(iter(loader))
        self._check_device_sampler(batch.get("sampler"))
        store_nts, sparse_nts = self._store_and_sparse_ntypes(
            batch["plan"])
        _, _, probe = self._make_device_fns_alltoall(
            batch["plan"], batch["batch_size"], store_nts, sparse_nts,
            collect_stats=True)
        tables = (self.feature_store.tables
                  if self.feature_store is not None else {})
        blocks = {k: self._put_batch(v) for k, v in batch["blocks"].items()}
        stats = jax.device_get(jax.jit(probe)(
            tables, self.device_sampler.tables, blocks, self.stepno))
        n = int(self.mesh.shape["data"])
        total_req = total_distinct = total_bytes = 0
        sites = []
        for e in stats:
            req = int(e["requests"][0])
            cap = int(e["capacity"][0])
            pb = int(e["payload_bytes"][0])
            fits = bool(min(int(v) for v in e["fits"]))
            distinct = sum(int(v) for v in e["distinct"])
            slots = cap if fits else req
            total_bytes += n * n * slots * (4 + pb)
            total_req += n * req
            total_distinct += distinct
            sites.append({"requests": req, "capacity": cap,
                          "payload_bytes": pb, "fits": fits,
                          "distinct": distinct})
        return {"exchanged_bytes_step": int(total_bytes),
                "dedup_ratio": (total_distinct / total_req
                                if total_req else 1.0),
                "requests": int(total_req),
                "distinct": int(total_distinct),
                "sites": sites}

    # ------------------------------------------------------------------
    def fit_batch(self, batch):
        if batch.get("sample_on_device"):
            return self._fit_batch_device(batch)
        feats, emb_ids, gather_idx = self._feats_for(batch)
        step = self._step_for(batch)
        aux_in = self._aux_inputs(batch)
        tables = self.feature_store.tables if gather_idx else {}
        self.params, self.opt_state, self.stepno, loss, out, gf = step(
            self.params, self.opt_state, self.stepno, feats,
            batch["arrays"], aux_in, gather_idx, tables)
        self._apply_sparse(emb_ids, gf)
        return float(loss), out

    def fit(self, train_dataloader, val_dataloader=None, num_epochs: int = 1,
            log_every: int = 0, verbose: bool = False, prefetch: int = 2,
            epoch_chunks: int = 1, eval_on_device: bool = False,
            checkpoint=None, async_checkpoint: bool = False):
        """Thin shim over the streaming epoch engine
        (``trainer.epoch_engine.StreamingEpochEngine`` — docs/pipeline.md
        §3f): any loader exposing stacked epochs (``epoch_blocks``, i.e.
        every repro dataloader, host- or device-sampling) trains through
        the engine's chunked scanned-epoch pipeline.  ``epoch_chunks``,
        ``eval_on_device``, ``checkpoint`` and ``async_checkpoint`` map
        straight onto the engine; ``log_every``/``prefetch`` only apply
        to the legacy per-batch path kept for plain batch iterables."""
        if (getattr(train_dataloader, "sample_on_device", False)
                or hasattr(train_dataloader, "epoch_blocks")):
            from repro.trainer.epoch_engine import StreamingEpochEngine
            engine = StreamingEpochEngine(
                self, train_dataloader, val_loader=val_dataloader,
                epoch_chunks=epoch_chunks, eval_on_device=eval_on_device,
                checkpoint=checkpoint, async_checkpoint=async_checkpoint,
                verbose=verbose)
            return engine.run(num_epochs)
        from repro.trainer.dataloading import PrefetchIterator
        for epoch in range(num_epochs):
            t0 = time.time()
            losses = []
            epoch_iter = (PrefetchIterator(train_dataloader, depth=prefetch)
                          if prefetch > 0 else train_dataloader)
            for bi, batch in enumerate(epoch_iter):
                loss, _ = self.fit_batch(batch)
                losses.append(loss)
                if log_every and (bi + 1) % log_every == 0 and verbose:
                    print(f"epoch {epoch} batch {bi + 1} loss "
                          f"{np.mean(losses[-log_every:]):.4f}")
            rec = {"epoch": epoch, "loss": float(np.mean(losses)),
                   "epoch_time_s": time.time() - t0}
            if val_dataloader is not None and self.evaluator is not None:
                rec[self.evaluator.name] = self.evaluate(val_dataloader)
            self.history.append(rec)
            if verbose:
                print(rec)
        return self.history

    def evaluate(self, dataloader) -> float:
        self.evaluator.reset()
        for batch in dataloader:
            self.eval_batch(batch)
        return self.evaluator.value()


# ---------------------------------------------------------------------------
class DeviceInferProgram:
    """One jitted inference-only device program: sample -> gather -> GNN
    -> task serve head over a fixed ``(batch_size,)``-padded seed vector
    of the task's serving ntype (``task_programs.serve_entry``).

    The static batch size is the jit cache key, so one compile covers
    every batch the serving batcher pads to it (``compiles()`` exposes
    the cache size for the one-compile-per-schema guard).  ``__call__``
    reads the trainer's *current* params/tables, so a restore after
    construction is picked up.  Serving runs single-device: build the
    trainer without a mesh (``run_config(serve=True)`` forces
    ``data_parallel: 1``)."""

    def __init__(self, trainer, batch_size: int):
        from repro.gnn.schema import schema_of_plan
        from repro.trainer.task_programs import serve_entry
        trainer._check_device_sampler(None)
        self.trainer = trainer
        self.ntype, head = serve_entry(trainer)
        self.batch_size = int(batch_size)
        sampler = trainer.device_sampler
        self.plan = sampler.plan_for({self.ntype: self.batch_size})
        self.schema = schema_of_plan(self.plan)
        store_nts, sparse_nts = trainer._store_and_sparse_ntypes(self.plan)
        model = trainer.model
        nt, plan, schema = self.ntype, self.plan, self.schema

        def infer(params, sparse_state, tables, csr, seeds, step):
            # seed-keyed draws: a seed's sampled subtree is a pure
            # function of its node id — invariant to batch composition,
            # padding, position, the step counter, and (therefore)
            # request splitting across serving replicas.  ``step`` stays
            # in the signature for staleness bookkeeping only.
            del step
            masks, dts, frontier = sampler.sample(csr, plan, {nt: seeds},
                                                  0, seed_keyed=True)
            arr = {"masks": masks, "delta_t": dts,
                   "feats": {**{m: tables[m][frontier[m]]
                                for m in store_nts},
                             **{m: sparse_state[m][0][frontier[m]]
                                for m in sparse_nts}}}
            emb = gnn_apply_blocks(params["gnn"], model, schema, arr)[nt]
            return emb, (emb if head is None else head(params, emb))

        self._jit = jax.jit(infer)
        # one-slot prefetch: (key, async device result) of a dispatched-
        # ahead batch.  jax dispatch is async, so ``prefetch`` costs the
        # host nothing; the next ``__call__`` with the same seed vector
        # returns the in-flight result instead of dispatching again.
        self._prefetched = None

    def _dispatch(self, seeds, step):
        tr = self.trainer
        tables = (tr.feature_store.tables
                  if tr.feature_store is not None else {})
        return self._jit(tr.params, tr._sparse_pack(), tables,
                         tr.device_sampler.tables, seeds,
                         jnp.asarray(step, jnp.int32))

    def _check_seeds(self, seeds):
        seeds = jnp.asarray(np.asarray(seeds), jnp.int32)
        if seeds.shape != (self.batch_size,):
            raise ValueError(
                f"expected a padded ({self.batch_size},) seed vector, got "
                f"shape {tuple(seeds.shape)} — pad with "
                f"repro.core.sampling.pad_seeds")
        return seeds

    def _key_of(self, seeds):
        # draws are seed-keyed (``step`` never reaches the trace), so the
        # seed bytes identify the result; params identity guards against
        # a restore/training step between prefetch and use
        return (np.asarray(seeds).tobytes(), id(self.trainer.params))

    def prefetch(self, seeds, step: int = 0):
        """Dispatch the program for an upcoming batch without waiting:
        the row gathers and GNN compute for batch k+1 run under batch
        k's host-side resolution (the serving analogue of the trainer's
        ``remote_prefetch`` scan pipeline).  Same jit, same static
        shape — never a new compile."""
        seeds = self._check_seeds(seeds)
        key = self._key_of(seeds)
        if self._prefetched is not None and self._prefetched[0] == key:
            return
        self._prefetched = (key, self._dispatch(seeds, step))

    def __call__(self, seeds, step: int = 0):
        """One padded batch -> device ``(emb, out)`` of shape
        ``(batch_size, ...)`` (rows beyond the real seeds are padding)."""
        seeds = self._check_seeds(seeds)
        if self._prefetched is not None:
            key, result = self._prefetched
            self._prefetched = None
            if key == self._key_of(seeds):
                return result
        return self._dispatch(seeds, step)

    def compiles(self) -> int:
        return self._jit._cache_size()


# ---------------------------------------------------------------------------
class GSgnnNodeTrainer(_TrainerBase):
    def __init__(self, model, target_ntype: str, num_classes: int = 0,
                 task: str = "node_classification", **kw):
        out_dim = num_classes if "classification" in task else 1
        super().__init__(model, task, out_dim=out_dim, **kw)
        self.target_ntype = target_ntype

    def _aux_inputs(self, batch):
        return {"labels": jnp.asarray(batch["labels"]),
                "mask": jnp.asarray(batch["seed_mask"])}

    def _task_loss(self, params, emb, aux_in, **_):
        out = decoder_apply(params["dec"], self.task, emb,
                            target_ntype=self.target_ntype)
        if "classification" in self.task:
            loss = _xent(out, aux_in["labels"], aux_in["mask"])
        else:
            loss = _mse(out, aux_in["labels"], aux_in["mask"])
        return loss, out

    def eval_batch(self, batch):
        feats, _ = self._eval_feats(batch)
        emb = self.embed_batch(batch, feats)
        out = decoder_apply(self.params["dec"], self.task, emb,
                            target_ntype=self.target_ntype)
        self.evaluator.update(out, batch["labels"], batch["seed_mask"])

    def embed_batch(self, batch, feats=None):
        if feats is None:
            feats, _ = self._eval_feats(batch)
        arr = dict(batch["arrays"])
        arr["feats"] = feats
        return gnn_apply_blocks(self.params["gnn"], self.model,
                                batch["schema"], arr)


# ---------------------------------------------------------------------------
class GSgnnEdgeTrainer(_TrainerBase):
    def __init__(self, model, target_etype, num_classes: int = 0,
                 task: str = "edge_classification", **kw):
        out_dim = num_classes if "classification" in task else 1
        super().__init__(model, task, out_dim=out_dim, **kw)
        self.target_etype = target_etype

    def _aux_inputs(self, batch):
        return {"labels": jnp.asarray(batch["labels"]),
                "mask": jnp.asarray(batch["seed_mask"])}

    def _task_loss(self, params, emb, aux_in, roles=None, **_):
        (snt, soff, slen), (dnt, doff, dlen) = roles[0], roles[1]
        src = jax.lax.slice_in_dim(emb[snt], soff, soff + slen, axis=0)
        dst = jax.lax.slice_in_dim(emb[dnt], doff, doff + dlen, axis=0)
        out = decoder_apply(params["dec"], self.task, emb, src_dst=(src, dst))
        if "classification" in self.task:
            loss = _xent(out, aux_in["labels"], aux_in["mask"])
        else:
            loss = _mse(out, aux_in["labels"], aux_in["mask"])
        return loss, out

    def eval_batch(self, batch):
        feats, _ = self._eval_feats(batch)
        arr = dict(batch["arrays"])
        arr["feats"] = feats
        emb = gnn_apply_blocks(self.params["gnn"], self.model,
                               batch["schema"], arr)
        (snt, soff, slen), (dnt, doff, dlen) = batch["roles"][:2]
        src = emb[snt][soff:soff + slen]
        dst = emb[dnt][doff:doff + dlen]
        out = decoder_apply(self.params["dec"], self.task, emb,
                            src_dst=(src, dst))
        self.evaluator.update(out, batch["labels"], batch["seed_mask"])


# ---------------------------------------------------------------------------
class GSgnnLinkPredictionTrainer(_TrainerBase):
    """LP with configurable loss (contrastive / cross-entropy) and the
    negative-sampling modes of the LP dataloader (§3.3.4).

    The host path takes the negatives the loader sampled; the device
    path (feed mode 3) instead draws them *in-jit* per
    ``neg_method``/``num_negatives`` (the LinkPredictionProgram's
    counter-based stream), so those two become trainer options here.
    ``local_nodes`` is the partition's dst-node set for ``local_joint``;
    ``exclude_target_edges`` drives the in-jit SpotTarget mask (the host
    loader owns its own flag)."""

    def __init__(self, model, target_etype, loss: str = "contrastive",
                 temperature: float = 0.1, neg_method: str = "joint",
                 num_negatives: int = 32, local_nodes=None,
                 exclude_target_edges: bool = True, **kw):
        super().__init__(model, "link_prediction", out_dim=0, **kw)
        self.target_etype = target_etype
        self.loss_kind = loss
        self.temperature = temperature
        self.neg_method = neg_method
        self.num_negatives = num_negatives
        self.local_nodes = local_nodes
        self.exclude_target_edges = exclude_target_edges
        self.etype_idx = [e[0] for e in model.etypes].index(
            "___".join(target_etype)) if model.etypes else None

    def _aux_inputs(self, batch):
        return {"neg_mask": jnp.asarray(batch["neg_mask"])}

    def _scores(self, params, emb, roles, neg_shape, k):
        (snt, soff, slen) = roles[0]
        (dnt, doff, dlen) = roles[1]
        src = jax.lax.slice_in_dim(emb[snt], soff, soff + slen, axis=0)
        dst = jax.lax.slice_in_dim(emb[dnt], doff, doff + dlen, axis=0)
        pos = lp_score(params["dec"], src, dst, self.etype_idx)
        B = slen
        if neg_shape == "per_edge":
            (nnt, noff, nlen) = roles[2]
            neg = jax.lax.slice_in_dim(emb[nnt], noff, noff + nlen, axis=0)
            neg = neg.reshape(B, k, -1)
            nsc = lp_score(params["dec"], src[:, None, :], neg, self.etype_idx)
        elif neg_shape == "shared":
            (nnt, noff, nlen) = roles[2]
            neg = jax.lax.slice_in_dim(emb[nnt], noff, noff + nlen, axis=0)
            if k >= B:  # one group: every edge scores all k shared negs
                nsc = lp_score(params["dec"], src[:, None, :],
                               neg[None, :, :], self.etype_idx)
            else:
                G = B // k
                nsc = lp_score(params["dec"],
                               src.reshape(G, k, 1, -1),
                               neg.reshape(G, 1, k, -1), self.etype_idx)
                nsc = nsc.reshape(B, k)
        else:  # in_batch: other dst nodes in the batch are the negatives
            nsc = lp_score_all(params["dec"], src, dst,
                               self.etype_idx)  # (B, B), one matmul
            # drop the diagonal (the positive itself): row i keeps cols i+1..i+B-1 mod B
            idx = (jnp.arange(B)[:, None] + jnp.arange(1, B)[None, :]) % B
            nsc = jnp.take_along_axis(nsc, idx, axis=1)  # (B, B-1)
        return pos, nsc

    def _lp_loss(self, pos, nsc, neg_mask):
        if self.loss_kind == "contrastive":
            loss = contrastive_lp_loss(pos, nsc, neg_mask, self.temperature)
        else:
            loss = cross_entropy_lp_loss(pos, nsc, neg_mask)
        return loss, (pos, nsc)

    def _task_loss(self, params, emb, aux_in, roles=None, neg_shape=None,
                   k=0):
        pos, nsc = self._scores(params, emb, roles, neg_shape, k)
        neg_mask = aux_in["neg_mask"]
        if neg_mask.shape != nsc.shape:
            neg_mask = jnp.ones(nsc.shape, bool)
        return self._lp_loss(pos, nsc, neg_mask)

    def eval_batch(self, batch):
        feats, _ = self._eval_feats(batch)
        arr = dict(batch["arrays"])
        arr["feats"] = feats
        emb = gnn_apply_blocks(self.params["gnn"], self.model,
                               batch["schema"], arr)
        pos, nsc = self._scores(self.params, emb, batch["roles"],
                                batch["neg_shape"], batch["num_negatives"])
        self.evaluator.update(pos, nsc)

    def _eval_update(self):
        # LP metrics fold (pos, neg_scores) — no label/mask blocks; host
        # eval_batch likewise scores every negative (no neg_mask)
        upd = self.evaluator.device_update()

        def apply(carry, out, aux_in):
            del aux_in
            num, den = carry
            pos, nsc = out
            return upd(num, den, pos, nsc, jnp.ones(nsc.shape, bool))
        return apply
