"""Fused dense ensemble prediction — the serving compiler's kernel tier.

Lowers a whole trained ensemble (every tree, every class) into ONE
dense program of path-condition contractions, the "Booster" accelerator
formulation (PAPERS.md arXiv:2011.02022) generalized from the per-tree
``_walk_raw_dense`` in :mod:`.tree`:

* the per-node feature lookup is a one-hot contraction (MXU) or a
  static-index take (CPU) over ALL ``T*(L-1)`` nodes at once;
* numeric thresholds are one broadcast compare against the stacked
  threshold row; NaN/default-direction/missing-type decision bits are
  folded into the same condition matrix;
* **categorical splits are a bitset-membership contraction**: the
  per-node ``cat_words`` uint32 bitsets unpack to a dense
  ``(cat_features * 32W, cat_nodes)`` 0/1 table and membership is the
  dot product of the row's category one-hot with that table — the
  FindInBitset bit-gather reformulated as AND+popcount on the MXU, so
  categorical ensembles no longer fall back to the sequential walk;
* leaf resolution is the satisfied-path-condition count: one batched
  contraction ``dec @ path_dir`` per tree axis and an EXACT
  ``relu(S - (plen_total - plen_right - 1))`` hit indicator (S is
  integer-valued and bounded by the path length, so the ReLU is a 0/1
  one-hot over leaves — no equality select needed on the matmul output);
* **leaf tables may be quantized** to i8/i16 codes with a per-tree
  scale, dequantized inside the final contraction (bit-controlled
  tolerance: per-tree error <= scale/2);
* piece-wise-linear leaves ride the same shape as a leaf-gather+matmul
  (arXiv:1802.05640): a dense ``(T, L, F)`` coefficient table contracts
  with the row block and the hit one-hot selects the active model, with
  the reference's NaN fallback to the plain leaf value.

The program contains NO ``while``/``scan`` loops (machine-checked by
the ``serve_dense`` trace-lint config) and, when sharded over the tree
axis, exactly one ``psum`` of the per-shard partial scores.

Host-side lowering lives in :func:`lower_ensemble`; the jitted entries
take the lowered arrays as ARGUMENTS so XLA's compile cache keys on
shapes/dtypes only — every model with the same shape signature shares
one compiled program per row bucket (the ``CompiledPredictor``
contract).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .tree import CAT_MASK, DEFAULT_LEFT_MASK, MISSING_NAN, Tree, TreeBatch

__all__ = ["DenseLoweringError", "DenseMeta", "DenseArrays",
           "lower_ensemble", "dense_predict_raw", "dense_predict_leaf",
           "make_sharded_predict", "dense_table_bytes",
           "stack_dense_arrays", "stacked_predict_raw",
           "make_stacked_sharded_predict",
           "CAT_TABLE_BUDGET", "LINEAR_TABLE_BUDGET"]

# Lowering budgets: a categorical bitset table or a linear-leaf
# coefficient table past these sizes would dominate HBM/cache for no
# win — the compiler falls back to the walk with a recorded reason.
CAT_TABLE_BUDGET = 128 << 20       # bytes of (Fc*C, NC) + top-bucket V block
LINEAR_TABLE_BUDGET = 256 << 20    # bytes of the dense (T, L, F) tables


class DenseLoweringError(ValueError):
    """The ensemble cannot (or should not) lower to the dense program.

    ``reason`` is a short machine-usable tag (``cat_table_budget``,
    ``linear_table_budget`` ...) surfaced by the serve compiler's
    fallback telemetry."""

    def __init__(self, reason: str, detail: str = "") -> None:
        self.reason = reason
        super().__init__(f"dense lowering unavailable ({reason})"
                         + (f": {detail}" if detail else ""))


class DenseMeta(NamedTuple):
    """Static (hashable) half of a lowered ensemble — the jit cache key
    next to the array shapes."""

    num_class: int
    num_trees: int            # REAL trees (before shard padding)
    has_cat: bool
    has_linear: bool
    leaf_bits: int            # 0 = exact f32 leaf table, else 8 | 16
    mxu: bool                 # True: one-hot/bf16 contractions (TPU);
                              # False: take/f32 lowering (CPU, interpret)


class DenseArrays(NamedTuple):
    """Device half of a lowered ensemble (a jax pytree; ``None`` fields
    collapse to empty subtrees so the jit cache keys on presence)."""

    split_feature: jnp.ndarray     # (T, Nn) int32 inner feature per node
    threshold: jnp.ndarray         # (T, Nn) f32
    dleft: jnp.ndarray             # (T, Nn) bool — default-left bit
    miss_nan: jnp.ndarray          # (T, Nn) bool — missing type == nan
    is_cat: jnp.ndarray            # (T, Nn) bool
    path_dir: jnp.ndarray          # (T, Nn, L) int8 — +1 left / -1 right
    qthresh: jnp.ndarray           # (T, L) f32 = plen_total - plen_right - 1
    leaf_codes: jnp.ndarray        # (T, L) f32 | int8 | int16
    leaf_scale: jnp.ndarray        # (T, 1) f32 dequant scale (1.0 when f32)
    class_onehot: jnp.ndarray      # (T, K) f32
    # categorical bitset contraction (None on cat-free ensembles)
    cat_feats: Optional[jnp.ndarray] = None       # (Fc,) int32 inner idx
    cat_table: Optional[jnp.ndarray] = None       # (Fc*C, NCp) f32|bf16
    node_cat_slot: Optional[jnp.ndarray] = None   # (T, Nn) int32, 0 = none
    # piece-wise-linear leaf tables (None on non-linear ensembles)
    lin_w: Optional[jnp.ndarray] = None           # (T, L, F) f32
    lin_mask: Optional[jnp.ndarray] = None        # (T, L, F) f32 0/1
    lin_const: Optional[jnp.ndarray] = None       # (T, L) f32
    lin_flag: Optional[jnp.ndarray] = None        # (T, 1) f32


def _unpack_bits32(words: np.ndarray) -> np.ndarray:
    """uint32 word vector -> (32 * len,) 0/1 float32 (LSB first)."""
    bits = (words[:, None] >> np.arange(32, dtype=np.uint32)[None, :]) & 1
    return bits.reshape(-1).astype(np.float32)


def dense_table_bytes(arrays: DenseArrays) -> int:
    """Total bytes of the lowered model tables (the ``info()`` figure)."""
    total = 0
    for a in arrays:
        if a is not None:
            total += a.size * a.dtype.itemsize
    return int(total)


def lower_ensemble(trees: List[Tree], num_class: int, num_features: int,
                   class_ids: Optional[List[int]] = None, *,
                   leaf_bits: int = 0, mxu: bool = False, shard: int = 1,
                   batch: Optional[TreeBatch] = None,
                   cat_budget: int = CAT_TABLE_BUDGET,
                   linear_budget: int = LINEAR_TABLE_BUDGET,
                   ) -> Tuple[DenseArrays, DenseMeta]:
    """Lower ``trees`` (classes interleaved ``t % num_class`` unless
    ``class_ids`` is given) into the fused dense program's arrays.

    ``shard > 1`` pads the tree axis to a multiple of ``shard`` with
    inert trees (unreachable leaves, zero class row) so the tree axis
    divides a mesh.  Raises :class:`DenseLoweringError` when a table
    would blow its budget."""
    if not trees:
        raise DenseLoweringError("no_trees")
    if leaf_bits not in (0, 8, 16):
        raise DenseLoweringError("leaf_bits", f"{leaf_bits} not in 0|8|16")
    b = batch if batch is not None else TreeBatch(trees)
    T = b.num_trees
    ml = b.max_leaves
    L = ml
    Nn = max(ml - 1, 1)
    if class_ids is None:
        class_ids = [t % num_class for t in range(T)]

    sf = np.zeros((T, Nn), np.int32)
    thr = np.zeros((T, Nn), np.float32)
    dt = np.zeros((T, Nn), np.uint8)
    sf[:, :max(ml - 1, 0)] = np.asarray(b.split_feature)
    thr[:, :max(ml - 1, 0)] = np.asarray(b.threshold)
    dt[:, :max(ml - 1, 0)] = np.asarray(b.decision_type)
    # only the first num_leaves-1 node slots of each tree are real; mask
    # the rest inert so stray decision bits on padding cannot mark a
    # nonexistent categorical node
    nl = np.asarray(b.num_leaves)
    real = np.arange(Nn)[None, :] < np.maximum(nl - 1, 0)[:, None]
    dt = np.where(real, dt, 0).astype(np.uint8)
    is_cat = (dt & CAT_MASK) != 0
    dleft = (dt & DEFAULT_LEFT_MASK) != 0
    miss_nan = (dt & (3 << 2)) == MISSING_NAN

    # path matrices: TreeBatch builds (T, Nn, L) host-side already
    pd = np.asarray(b.path_dir, np.int8)
    qt = (np.asarray(b.plen_total, np.float32) -
          np.asarray(b.plen_right, np.float32) - 1.0)

    # quantized leaf table: i8/i16 codes + per-tree scale, dequantized
    # in the final contraction (bit-controlled tolerance <= scale/2)
    leaf = np.asarray(b.leaf_value, np.float32)
    if leaf_bits:
        qmax = float((1 << (leaf_bits - 1)) - 1)
        maxabs = np.max(np.abs(leaf), axis=1)
        scale = np.where(maxabs > 0, maxabs / qmax, 1.0).astype(np.float32)
        codes = np.rint(leaf / scale[:, None]).astype(
            np.int8 if leaf_bits == 8 else np.int16)
    else:
        scale = np.ones(T, np.float32)
        codes = leaf

    cls = np.zeros((T, num_class), np.float32)
    cls[np.arange(T), np.asarray(class_ids, np.int64)] = 1.0

    # --- categorical bitset -> dense membership table ----------------------
    has_cat = bool(is_cat.any())
    cat_feats = cat_table = node_slot = None
    if has_cat:
        words = np.asarray(b.cat_words)               # (T, Nn', W)
        W = words.shape[2]
        C = 32 * W
        feats = np.unique(sf[is_cat])
        slot_of = {int(f): j for j, f in enumerate(feats)}
        Fc = len(feats)
        cat_idx = np.argwhere(is_cat)                  # (NC, 2)
        NC = len(cat_idx)
        NCp = max(8, -(-NC // 8) * 8)
        top_bucket = 4096
        table_b = 4 * Fc * C * NCp + 4 * top_bucket * Fc * C
        if table_b > cat_budget:
            raise DenseLoweringError(
                "cat_table_budget",
                f"{Fc} cat features x {C} categories x {NC} cat nodes "
                f"needs ~{table_b >> 20} MiB (> {cat_budget >> 20} MiB)")
        K = np.zeros((Fc * C, NCp), np.float32)
        node_slot = np.zeros((T, Nn), np.int32)
        for m, (ti, ni) in enumerate(cat_idx):
            j = slot_of[int(sf[ti, ni])]
            K[j * C:(j + 1) * C, m] = _unpack_bits32(
                words[ti, ni] if ni < words.shape[1]
                else np.zeros(W, np.uint32))
            node_slot[ti, ni] = m + 1
        cat_feats = feats.astype(np.int32)
        cat_table = K.astype(np.float32)

    # --- piece-wise-linear leaves as dense (T, L, F) tables ----------------
    has_linear = bool(b.has_linear)
    lin_w = lin_mask = lin_const = lin_flag = None
    if has_linear:
        table_b = 2 * 4 * T * L * num_features
        if table_b > linear_budget:
            raise DenseLoweringError(
                "linear_table_budget",
                f"(T={T}, L={L}, F={num_features}) linear tables need "
                f"~{table_b >> 20} MiB (> {linear_budget >> 20} MiB)")
        lin_w = np.zeros((T, L, num_features), np.float32)
        lin_mask = np.zeros((T, L, num_features), np.float32)
        lin_const = np.zeros((T, L), np.float32)
        lin_flag = np.zeros((T, 1), np.float32)
        for ti, t in enumerate(trees):
            if not t.is_linear:
                continue
            lin_flag[ti, 0] = 1.0
            lin_const[ti, :len(t.leaf_const)] = np.asarray(
                t.leaf_const, np.float32)
            feats_per_leaf = (t.leaf_features_inner
                              if t.leaf_features_inner is not None
                              else t.leaf_features)
            for leaf_i, (fs, cs) in enumerate(zip(feats_per_leaf,
                                                  t.leaf_coeff)):
                for f, c in zip(fs, cs):
                    lin_w[ti, leaf_i, f] += np.float32(c)
                    lin_mask[ti, leaf_i, f] = 1.0

    # --- shard padding: inert trees make the tree axis divide a mesh -------
    if shard > 1 and T % shard:
        pad = shard - T % shard
        sf = np.pad(sf, ((0, pad), (0, 0)))
        thr = np.pad(thr, ((0, pad), (0, 0)))
        dleft = np.pad(dleft, ((0, pad), (0, 0)))
        miss_nan = np.pad(miss_nan, ((0, pad), (0, 0)))
        is_cat = np.pad(is_cat, ((0, pad), (0, 0)))
        pd = np.pad(pd, ((0, pad), (0, 0), (0, 0)))
        qt = np.pad(qt, ((0, pad), (0, 0)), constant_values=np.float32(1e9))
        codes = np.pad(codes, ((0, pad), (0, 0)))
        scale = np.pad(scale, (0, pad), constant_values=np.float32(1.0))
        cls = np.pad(cls, ((0, pad), (0, 0)))
        if node_slot is not None:
            node_slot = np.pad(node_slot, ((0, pad), (0, 0)))
        if lin_w is not None:
            lin_w = np.pad(lin_w, ((0, pad), (0, 0), (0, 0)))
            lin_mask = np.pad(lin_mask, ((0, pad), (0, 0), (0, 0)))
            lin_const = np.pad(lin_const, ((0, pad), (0, 0)))
            lin_flag = np.pad(lin_flag, ((0, pad), (0, 0)))

    j = jnp.asarray
    arrays = DenseArrays(
        split_feature=j(sf), threshold=j(thr), dleft=j(dleft),
        miss_nan=j(miss_nan), is_cat=j(is_cat), path_dir=j(pd),
        qthresh=j(qt), leaf_codes=j(codes),
        leaf_scale=j(scale.reshape(-1, 1)), class_onehot=j(cls),
        cat_feats=None if cat_feats is None else j(cat_feats),
        cat_table=None if cat_table is None else j(
            cat_table.astype(np.float32)),
        node_cat_slot=None if node_slot is None else j(node_slot),
        lin_w=None if lin_w is None else j(lin_w),
        lin_mask=None if lin_mask is None else j(lin_mask),
        lin_const=None if lin_const is None else j(lin_const),
        lin_flag=None if lin_flag is None else j(lin_flag))
    meta = DenseMeta(num_class=num_class, num_trees=T, has_cat=has_cat,
                     has_linear=has_linear, leaf_bits=leaf_bits,
                     mxu=bool(mxu))
    return arrays, meta


# ---------------------------------------------------------------------------
# the fused program
# ---------------------------------------------------------------------------

def _node_values(X, flat_feature, mxu: bool):
    """(N, T*Nn) per-node row values: a one-hot contraction on the MXU
    (exact f32 at Precision.HIGHEST — a bf16-rounded value could flip a
    near-threshold decision), a static-index take elsewhere (the
    indices are model constants, so XLA lowers a plain column copy)."""
    if not mxu:
        return jnp.take(X, flat_feature, axis=1)
    f_count = X.shape[1]
    onehot = (jnp.arange(f_count, dtype=jnp.int32)[:, None] ==
              flat_feature[None, :]).astype(jnp.float32)
    return jax.lax.dot_general(X, onehot, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def _decision_matrix(X, A: DenseArrays, meta: DenseMeta):
    """The fused condition matrix ``dec`` (N, T, Nn) in {0,1}: numeric
    broadcast compares, NaN/default-direction bits, and the categorical
    bitset contraction, all folded in."""
    n = X.shape[0]
    T, Nn = A.split_feature.shape
    flat_sf = A.split_feature.reshape(-1)
    P = _node_values(jnp.nan_to_num(X), flat_sf, meta.mxu)
    isn = _node_values(jnp.isnan(X).astype(jnp.float32), flat_sf,
                       meta.mxu) > 0.5
    dec = P <= A.threshold.reshape(-1)[None, :]
    if meta.has_cat:
        Fc = A.cat_feats.shape[0]
        C = A.cat_table.shape[0] // Fc
        # the row's category one-hot over (feature, category); NaN and
        # non-integer / out-of-range values one-hot to all-zero rows,
        # which contract to "not a member" (go right) exactly like the
        # reference FindInBitset out-of-range path
        Xc = jnp.take(X, A.cat_feats, axis=1)
        Xc = jnp.where(jnp.isnan(Xc), -1.0, Xc)
        V = (Xc[:, :, None] ==
             jnp.arange(C, dtype=X.dtype)[None, None, :])
        V = V.reshape(n, Fc * C)
        # membership = AND+popcount as a dense contraction: the row
        # one-hot dotted with the unpacked per-node bitset table
        if meta.mxu:
            member = jax.lax.dot_general(
                V.astype(jnp.bfloat16), A.cat_table.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            member = jax.lax.dot_general(
                V.astype(jnp.float32), A.cat_table,
                (((1,), (0,)), ((), ())))
        member = jnp.concatenate(
            [jnp.zeros((n, 1), member.dtype), member], axis=1)
        member = jnp.take(member, A.node_cat_slot.reshape(-1), axis=1)
        dec = jnp.where(A.is_cat.reshape(-1)[None, :], member > 0.5, dec)
    # NaN routing: categorical and missing-nan numeric nodes take the
    # default direction; other numeric nodes already compare the
    # sanitized 0.0 (the reference's missing-zero path)
    nan_default = (A.miss_nan | A.is_cat).reshape(-1)
    dec = jnp.where(isn & nan_default[None, :],
                    A.dleft.reshape(-1)[None, :], dec)
    return dec.reshape(n, T, Nn)


def _hit_matrix(dec, A: DenseArrays, meta: DenseMeta):
    """(T, N, L) EXACT 0/1 leaf one-hot via the satisfied-condition
    count.  ``S`` counts correct turns along each leaf's root path
    (integer-valued, <= path length), so ``relu(S - (len-1))`` is 1
    exactly on the reached leaf and 0 elsewhere — the equality test of
    the per-tree dense walk without a select on the matmul output."""
    acc = jnp.bfloat16 if meta.mxu else jnp.float32
    dec_t = jnp.transpose(dec, (1, 0, 2)).astype(acc)       # (T, N, Nn)
    S = jax.lax.dot_general(dec_t, A.path_dir.astype(acc),
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    # right-expected nodes contribute (1 - dec); their +1-per-right
    # constant is folded into qthresh = plen_total - plen_right - 1
    return jax.nn.relu(S - A.qthresh[:, None, :])


def _per_tree_scores(X, hit, A: DenseArrays, meta: DenseMeta):
    """(T, N) per-tree outputs: quantized leaf tables dequantized in the
    contraction; linear leaves as leaf-gather + matmul with the NaN
    fallback."""
    leaf_deq = A.leaf_codes.astype(jnp.float32) * A.leaf_scale  # (T, L)
    if not meta.has_linear:
        # hit is an exact one-hot, so the select-free product-sum picks
        # the reached leaf's value exactly (one nonzero term)
        return jnp.sum(hit * leaf_deq[:, None, :], axis=2)
    Xs = jnp.nan_to_num(X)
    isnX = jnp.isnan(X).astype(jnp.float32)
    lin_vals = jax.lax.dot_general(
        A.lin_w, Xs, (((2,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)                 # (T, L, N)
    lin_nan = jax.lax.dot_general(
        A.lin_mask, isnX, (((2,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST) > 0.5           # (T, L, N)
    lin_out = A.lin_const[:, :, None] + lin_vals
    use_lin = (A.lin_flag[:, :, None] > 0) & ~lin_nan
    vals = jnp.where(use_lin, lin_out, leaf_deq[:, :, None])  # (T, L, N)
    return jnp.sum(hit * jnp.transpose(vals, (0, 2, 1)), axis=2)


def _dense_raw(X, A: DenseArrays, meta: DenseMeta):
    """(N, K) raw scores — the whole ensemble in one loop-free program."""
    dec = _decision_matrix(X, A, meta)
    hit = _hit_matrix(dec, A, meta)
    per_tree = _per_tree_scores(X, hit, A, meta)             # (T, N)
    return jax.lax.dot_general(per_tree.T, A.class_onehot,
                               (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("meta",))
def dense_predict_raw(X, arrays: DenseArrays, meta: DenseMeta):
    """Jitted fused-ensemble raw prediction: (N, num_class) f32."""
    return _dense_raw(X, arrays, meta)


@functools.partial(jax.jit, static_argnames=("meta",))
def dense_predict_leaf(X, arrays: DenseArrays, meta: DenseMeta):
    """Jitted fused pred-leaf: (N, T) int32 leaf index per REAL tree
    (callers slice away shard-padding trees)."""
    dec = _decision_matrix(X, arrays, meta)
    hit = _hit_matrix(dec, arrays, meta)
    return jnp.argmax(hit, axis=2).astype(jnp.int32).T


def stack_dense_arrays(arrays_list):
    """Stack M same-signature models' lowered tables on a NEW leading
    model axis: every (T, ...) table becomes (M, T, ...).  Requires
    identical shapes/dtypes AND identical optional-field presence (both
    guaranteed by an equal ``DenseExecutable.signature``), so the None
    fields collapse consistently and the tree structures match."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *arrays_list)


@functools.partial(jax.jit, static_argnames=("meta",))
def stacked_predict_raw(Xs, stacked: DenseArrays, meta: DenseMeta):
    """(M, N, K) raw scores for M same-signature models in ONE fused
    launch — the zoo's cross-model hot path.  ``Xs`` is (M, N, F): each
    lane carries its own tenant's padded micro-batch.  vmap over the
    model axis turns every contraction of :func:`_dense_raw` into a
    batched contraction of the same per-slice shape, so each lane's
    scores are bitwise identical to a solo :func:`dense_predict_raw`
    call (asserted by the zoo parity tests)."""
    return jax.vmap(lambda x, a: _dense_raw(x, a, meta))(Xs, stacked)


def _stacked_shard_specs(stacked: DenseArrays, axis: str):
    """PartitionSpec tree for tree-axis sharding of STACKED tables: the
    model axis is leading and never sharded; the tree axis (now dim 1)
    splits; the categorical contraction tables stay replicated."""
    from jax.sharding import PartitionSpec as P
    replicated = ("cat_feats", "cat_table")
    vals = {}
    for name in stacked._fields:
        a = getattr(stacked, name)
        if a is None:
            vals[name] = None
        elif name in replicated:
            vals[name] = P()
        else:
            vals[name] = P(None, axis)
    return DenseArrays(**vals)


def make_stacked_sharded_predict(stacked: DenseArrays, meta: DenseMeta,
                                 mesh, axis: str = "trees"):
    """Tree-sharded stacked prediction: per-shard partials over every
    model lane and exactly ONE psum of the (M, N, K) partial scores —
    the ``serve/zoo_stack/score_psum`` collective contract (one psum
    per STACK, not one per tenant; declared in serve/zoo.py)."""
    from jax.sharding import PartitionSpec as P
    from ..telemetry.train_record import note_collective

    def body(Xs, A):
        part = jax.vmap(lambda x, a: _dense_raw(x, a, meta))(Xs, A)
        note_collective("serve/zoo_stack/score_psum", "psum", part)
        return jax.lax.psum(part, axis)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), _stacked_shard_specs(stacked, axis)),
        out_specs=P(), check_vma=False))


def _shard_specs(arrays: DenseArrays, axis: str):
    """PartitionSpec tree for the tree-axis sharding: every (T, ...)
    table splits on ``axis``; the categorical contraction tables are
    replicated (every shard tests its own nodes against the full
    category space)."""
    from jax.sharding import PartitionSpec as P
    replicated = ("cat_feats", "cat_table")
    vals = {}
    for name in arrays._fields:
        a = getattr(arrays, name)
        if a is None:
            vals[name] = None
        elif name in replicated:
            vals[name] = P()
        else:
            vals[name] = P(axis)
    return DenseArrays(**vals)


def make_sharded_predict(arrays: DenseArrays, meta: DenseMeta, mesh,
                         axis: str = "trees"):
    """pjit-sharded fused prediction over the tree axis for ensembles
    too wide for one device: per-shard partial scores and exactly ONE
    psum of the (N, K) partials — the declared
    ``serve/dense_predict/score_psum`` collective contract."""
    from jax.sharding import PartitionSpec as P
    from ..telemetry.train_record import note_collective

    def body(X, A):
        part = _dense_raw(X, A, meta)
        note_collective("serve/dense_predict/score_psum", "psum", part)
        return jax.lax.psum(part, axis)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), _shard_specs(arrays, axis)),
        out_specs=P(), check_vma=False))
