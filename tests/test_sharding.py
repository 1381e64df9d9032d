"""Divisibility-aware specs and the ragged cross-shard exchange.

Two layers of contract:

- spec construction (`maybe_axis` / `best_spec` / `shard_rows`) must fall
  back to replication — or, with ``pad=True``, zero-pad — whenever a mesh
  axis does not divide a dimension, and emitted specs must be in GSPMD's
  trimmed form so jit caches never fork on equivalent placements;
- the :class:`repro.common.sharding.RaggedExchange` primitive must be
  *semantically invisible*: for any ownership layout and any request set
  (all-local, all-remote, duplicated, skewed), gathering through the
  exchange is bit-identical to indexing the replicated table, and the
  gradient scatter-back matches the dense ``np.add.at`` reference.

The exchange tests run on 8 fake CPU devices in a subprocess because
``--xla_force_host_platform_device_count`` must be set before the first
jax import (conftest.py keeps the main test process single-device).
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# spec construction (in-process; mesh.shape is the only thing consulted)
# ---------------------------------------------------------------------------
def _fake_mesh(**axes):
    """maybe_axis/best_spec/axis_size read only ``mesh.shape``."""
    return types.SimpleNamespace(shape=dict(axes))


def test_maybe_axis_one_sized_axis_always_divides():
    from repro.common.sharding import maybe_axis
    mesh = _fake_mesh(data=1)
    # a 1-sized axis divides every dim, including 0 and primes
    for dim in (0, 1, 7, 49155):
        assert maybe_axis(mesh, "data", dim) == "data"


def test_maybe_axis_compound_shrinks_past_one_sized():
    from repro.common.sharding import maybe_axis
    mesh = _fake_mesh(pod=1, data=8)
    # ("pod", "data") is 8-way: dim 12 -> shrink to ("pod",) which is
    # 1-way and always divides
    assert maybe_axis(mesh, ("pod", "data"), 12) == "pod"
    assert maybe_axis(mesh, ("pod", "data"), 16) == ("pod", "data")


def test_maybe_axis_indivisible_replicates():
    from repro.common.sharding import maybe_axis
    mesh = _fake_mesh(data=8)
    assert maybe_axis(mesh, "data", 12) is None
    assert maybe_axis(mesh, "data", 16) == "data"


def test_best_spec_indivisible_rows_fall_back():
    from jax.sharding import PartitionSpec as P
    from repro.common.sharding import best_spec
    mesh = _fake_mesh(data=8)
    # 53 rows on an 8-way axis: replicate (and trim the trailing None —
    # an untrimmed spec would fork GSPMD jit caches)
    assert best_spec(mesh, (53, 4), ("data", None)) == P()
    assert best_spec(mesh, (56, 4), ("data", None)) == P("data")


def test_best_spec_axis_used_once():
    from jax.sharding import PartitionSpec as P
    from repro.common.sharding import best_spec
    mesh = _fake_mesh(data=8)
    # the axis is consumed by dim 0; dim 1 must replicate even though 8
    # divides it
    assert best_spec(mesh, (16, 8), ("data", "data")) == P("data")


def test_padded_row_count():
    from repro.common.sharding import padded_row_count
    assert padded_row_count(53, 8) == 56
    assert padded_row_count(56, 8) == 56
    assert padded_row_count(1, 8) == 8
    assert padded_row_count(0, 8) == 0


# ---------------------------------------------------------------------------
# ragged exchange (8 fake devices, subprocess)
# ---------------------------------------------------------------------------
_EXCHANGE_SCRIPT = r"""
import json
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.common.sharding import RaggedExchange, shard_rows

S = 8
mesh = Mesh(np.array(jax.devices()[:S]), ("data",))


def run_case(rows, dim, n_req, idx):
    # gather idx through the exchange against a pad-sharded table and
    # scatter grads back; check both against dense references
    rng = np.random.default_rng(rows * 1009 + n_req)
    table = rng.normal(size=(rows, dim)).astype(np.float32)
    grads = rng.normal(size=(S, n_req, dim)).astype(np.float32)
    tbl = shard_rows(mesh, table, "data", pad=True)
    rows_pad = tbl.shape[0]
    rps = rows_pad // S

    def local(tl, il, gl):
        ex = RaggedExchange(il.reshape(-1), axis_name="data",
                            n_shards=S, rows_per_shard=rps)
        out = ex.gather(tl)
        payload, lids, mask = ex.scatter_rows(gl.reshape(-1, dim))
        acc = jnp.zeros_like(tl).at[lids.reshape(-1)].add(
            jnp.where(mask[..., None], payload, 0).reshape(-1, dim))
        return out[None], acc

    f = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False))
    sh = NamedSharding(mesh, P("data"))
    out, acc = f(tbl, jax.device_put(idx, sh), jax.device_put(grads, sh))
    # gather must be bit-identical to the replicated (padded) gather
    pad_tbl = np.zeros((rows_pad, dim), np.float32)
    pad_tbl[:rows] = table
    ref_gather = pad_tbl[idx.reshape(-1)].reshape(S, n_req, dim)
    gather_ok = np.array_equal(np.asarray(out), ref_gather)
    # scatter-back must match the dense duplicate-summing reference
    ref_acc = np.zeros((rows_pad, dim), np.float32)
    np.add.at(ref_acc, idx.reshape(-1), grads.reshape(-1, dim))
    scatter_ok = np.allclose(np.asarray(acc), ref_acc, atol=1e-5)
    return gather_ok, scatter_ok


results = {}
rng = np.random.default_rng(0)

# property sweep: random row counts (divisible and not), random requests
# with duplicates, several sizes
ok_g = ok_s = True
for rows, n_req in [(53, 16), (64, 16), (8, 4), (200, 32), (17, 8)]:
    idx = rng.integers(0, rows, size=(S, n_req)).astype(np.int32)
    g, s = run_case(rows, 3, n_req, idx)
    ok_g &= g
    ok_s &= s
results["random"] = bool(ok_g and ok_s)

# all-rows-local extreme: every shard asks only for rows it owns
rows, n_req = 64, 16
rps = rows // S
idx_local = (np.arange(S)[:, None] * rps
             + rng.integers(0, rps, size=(S, n_req))).astype(np.int32)
results["all_local"] = all(run_case(rows, 3, n_req, idx_local))

# all-rows-remote extreme: every shard asks only for the next shard's rows
idx_remote = (((np.arange(S)[:, None] + 1) % S) * rps
              + rng.integers(0, rps, size=(S, n_req))).astype(np.int32)
results["all_remote"] = all(run_case(rows, 3, n_req, idx_remote))

# worst-case skew: every shard's ENTIRE request list is owned by shard 0
# (static shapes must absorb maximal ownership imbalance)
idx_skew = rng.integers(0, rps, size=(S, n_req)).astype(np.int32)
results["skew_to_one"] = all(run_case(rows, 3, n_req, idx_skew))

# duplicate-heavy: one hot row requested by everybody, many times
idx_dup = np.full((S, n_req), 11, np.int32)
results["duplicates"] = all(run_case(rows, 3, n_req, idx_dup))

print("RESULT:" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def exchange_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", _EXCHANGE_SCRIPT],
                          capture_output=True, text=True, env=env,
                          cwd=_ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    return json.loads(line[len("RESULT:"):])


def test_exchange_gather_matches_replicated_random(exchange_results):
    assert exchange_results["random"]


def test_exchange_all_local_extreme(exchange_results):
    assert exchange_results["all_local"]


def test_exchange_all_remote_extreme(exchange_results):
    assert exchange_results["all_remote"]


def test_exchange_worst_case_ownership_skew(exchange_results):
    assert exchange_results["skew_to_one"]


def test_exchange_duplicate_requests(exchange_results):
    assert exchange_results["duplicates"]


# ---------------------------------------------------------------------------
# dedup composition (docs/pipeline.md §3e): unique_rows + RaggedExchange
# + wire-dtype payloads, on the same 8-fake-device subprocess rig
# ---------------------------------------------------------------------------
_DEDUP_SCRIPT = r"""
import json
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.common.sharding import (RaggedExchange, dedup_gather,
                                   dedup_capacity, shard_rows)

S = 8
mesh = Mesh(np.array(jax.devices()[:S]), ("data",))


def gathers(rows, dim, idx, capacity=None, wire=None):
    # (dedup_gather result, plain RaggedExchange result) for one layout
    rng = np.random.default_rng(rows * 7919 + idx.size)
    table = rng.normal(size=(rows, dim)).astype(np.float32)
    tbl = shard_rows(mesh, table, "data", pad=True)
    rps = tbl.shape[0] // S

    def local(tl, il):
        ids = il.reshape(-1)
        ded = dedup_gather(ids, tl, axis_name="data", n_shards=S,
                           rows_per_shard=rps, capacity=capacity,
                           wire_dtype=wire)
        ex = RaggedExchange(ids, axis_name="data", n_shards=S,
                            rows_per_shard=rps)
        return ded[None], ex.gather(tl, wire_dtype=wire)[None]

    f = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data")), check_vma=False))
    sh = NamedSharding(mesh, P("data"))
    ded, plain = f(tbl, jax.device_put(idx, sh))
    rows_pad = tbl.shape[0]
    pad_tbl = np.zeros((rows_pad, dim), np.float32)
    pad_tbl[:rows] = table
    if wire is not None:
        pad_tbl = pad_tbl.astype(wire).astype(np.float32)
    ref = pad_tbl[idx.reshape(-1)].reshape(idx.shape + (dim,))
    return np.asarray(ded), np.asarray(plain), ref


results = {}
rng = np.random.default_rng(1)
# dim 16 keeps the wire row at/above DEDUP_MIN_PAYLOAD_BYTES even at
# bf16 (32 B), so the default-capacity cases exercise the dedup branch
# rather than the narrow-payload static fallback
rows, dim, n_req = 64, 16, 32

# duplicate-heavy frontier: dedup on == dedup off == replicated, bitwise
idx = rng.integers(0, 8, size=(S, n_req)).astype(np.int32)
ded, plain, ref = gathers(rows, dim, idx)
results["dup_heavy"] = (np.array_equal(ded, plain)
                        and np.array_equal(ded, ref))

# all-duplicate frontier: one row requested by every slot of every shard
idx_all = np.full((S, n_req), 13, np.int32)
ded, plain, ref = gathers(rows, dim, idx_all)
results["all_dup"] = (np.array_equal(ded, plain)
                      and np.array_equal(ded, ref))

# random frontiers at several shapes: dedup-on vs dedup-off parity
ok = True
for rows_c, n_c in [(53, 16), (200, 24), (17, 8)]:
    idx_c = rng.integers(0, rows_c, size=(S, n_c)).astype(np.int32)
    ded, plain, ref = gathers(rows_c, dim, idx_c)
    ok &= np.array_equal(ded, plain) and np.array_equal(ded, ref)
results["random_parity"] = bool(ok)

# overflow: capacity below the distinct count on every shard -> the
# in-jit cond falls back to the plain exchange (identical, never wrong)
idx_wide = np.stack([rng.permutation(rows)[:n_req]
                     for _ in range(S)]).astype(np.int32)
ded, plain, ref = gathers(rows, dim, idx_wide, capacity=4)
results["overflow_fallback"] = (np.array_equal(ded, plain)
                                and np.array_equal(ded, ref))

# mixed fit: some shards' frontiers fit the capacity, others overflow —
# the gathered-count vote must pick ONE branch mesh-wide (still exact)
idx_mix = idx_wide.copy()
idx_mix[::2] = 13            # even shards: all-duplicate (fits easily)
ded, plain, ref = gathers(rows, dim, idx_mix,
                          capacity=dedup_capacity(n_req))
results["mixed_fit"] = (np.array_equal(ded, plain)
                        and np.array_equal(ded, ref))

# payload-width policy: a narrow-row table (under DEDUP_MIN_PAYLOAD_BYTES
# on the wire) statically resolves to the plain exchange — no cond, no
# unique pass — while a wide-row table keeps the in-jit branch; both
# still return exact rows (dup_heavy/random cases above)
def _traced(dimw):
    def local(tl, il):
        return dedup_gather(il.reshape(-1), tl, axis_name="data",
                            n_shards=S, rows_per_shard=8)[None]
    t = jnp.zeros((64, dimw), jnp.float32)
    return str(jax.make_jaxpr(shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P("data"), check_vma=False))(t, idx))

results["narrow_payload_static_plain"] = (
    "cond" not in _traced(3) and "cond" in _traced(16))

# bf16 wire payloads: exact per row (one owner -> the psum adds one
# nonzero bf16 value; fp32 restore is exact widening), with and without
# dedup, against the cast-restore reference
ded, plain, ref = gathers(rows, dim, idx, wire=jnp.bfloat16)
results["bf16_wire"] = (np.array_equal(ded, plain)
                        and np.array_equal(ded, ref))

print("RESULT:" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def dedup_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", _DEDUP_SCRIPT],
                          capture_output=True, text=True, env=env,
                          cwd=_ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    return json.loads(line[len("RESULT:"):])


def test_dedup_duplicate_heavy_bitwise(dedup_results):
    assert dedup_results["dup_heavy"]


def test_dedup_all_duplicate_frontier(dedup_results):
    assert dedup_results["all_dup"]


def test_dedup_on_off_parity_random(dedup_results):
    assert dedup_results["random_parity"]


def test_dedup_overflow_falls_back_exactly(dedup_results):
    assert dedup_results["overflow_fallback"]


def test_dedup_mixed_fit_votes_one_branch(dedup_results):
    assert dedup_results["mixed_fit"]


def test_dedup_narrow_payload_resolves_to_plain(dedup_results):
    assert dedup_results["narrow_payload_static_plain"]


def test_bf16_wire_payload_exact_per_row(dedup_results):
    assert dedup_results["bf16_wire"]


# ---------------------------------------------------------------------------
# padded shard_rows round-trip (single device: pad must be a no-op)
# ---------------------------------------------------------------------------
def test_shard_rows_pad_noop_on_one_device():
    import jax
    from jax.sharding import Mesh
    from repro.common.sharding import shard_rows
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    out = shard_rows(mesh, x, "data", pad=True)
    assert out.shape == (6, 2)
    np.testing.assert_array_equal(np.asarray(out), x)
