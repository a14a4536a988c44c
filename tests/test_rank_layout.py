"""lambdarank's query layout (objective/rank.py): queries in length buckets,
each bucket's truncated (T, M) pair block, one gather back to row order.

The lambdas and hessians are held to a float64 per-query loop written as
rank_objective.hpp writes it (to float32 rounding) and to the (Q, M, M)
pair tensor the objective evaluated before the layout, on seeded
heavy-tailed query lengths with the edge cases a bucketed, truncated block
can get wrong: a query of one document, queries shorter than, equal to and
far longer than the truncation level, all labels equal, tied scores, lengths
on both sides of a bucket edge.  The layout's record is held to a direct
count; rank_xendcg, which keeps its own padding, still trains."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata
from lightgbm_tpu.objective import rank
from lightgbm_tpu.objective.rank import (LambdarankNDCG, QueryLayout, bucket_width,
                                         pad_queries)

GAIN = np.asarray(Config({"objective": "lambdarank"}).label_gain, np.float64)


def loop_lambdas(score, label, qb, trunc=30, sig=1.0, norm=True):
    """rank_objective.hpp's GetGradientsForOneQuery, pair by pair, float64."""
    lam, hes = np.zeros(len(score)), np.zeros(len(score))
    for q in range(len(qb) - 1):
        s0, cnt = qb[q], qb[q + 1] - qb[q]
        if cnt == 0:
            continue
        sc = score[s0:s0 + cnt].astype(np.float64)
        lb = label[s0:s0 + cnt].astype(np.int64)
        top = np.sort(lb)[::-1][:trunc]
        mdcg = sum(GAIN[l] / np.log2(k + 2.0) for k, l in enumerate(top))
        inv = 1.0 / mdcg if mdcg > 0 else 0.0
        idx = sorted(range(cnt), key=lambda a: -sc[a])       # stable
        best, worst = sc[idx[0]], sc[idx[cnt - 1]]
        L, H, total = np.zeros(cnt), np.zeros(cnt), 0.0
        for i in range(min(cnt - 1, trunc)):
            for j in range(i + 1, cnt):
                if lb[idx[i]] == lb[idx[j]]:
                    continue
                hr, lr = (i, j) if lb[idx[i]] > lb[idx[j]] else (j, i)
                h, lo = idx[hr], idx[lr]
                ds = sc[h] - sc[lo]
                dn = (GAIN[lb[h]] - GAIN[lb[lo]]) * \
                    abs(1 / np.log2(hr + 2.0) - 1 / np.log2(lr + 2.0)) * inv
                if norm and best != worst:
                    dn /= float(np.float32(0.01)) + abs(ds)
                p = 1.0 / (1.0 + np.exp(sig * ds))
                ph = p * (1.0 - p)
                p *= -sig * dn
                ph *= sig * sig * dn
                L[lo] -= p
                H[lo] += ph
                L[h] += p
                H[h] += ph
                total -= 2 * p
        if norm and total > 0:
            L *= np.log2(1 + total) / total
            H *= np.log2(1 + total) / total
        lam[s0:s0 + cnt], hes[s0:s0 + cnt] = L, H
    return lam, hes


def full_pair_lambdas(score, label, qb, trunc=30, sig=1.0, norm=True):
    """The (Q, M, M) form: every query padded to the longest, every pair
    evaluated, scattered back to the rows."""
    idx, valid = pad_queries(qb)
    q, m = idx.shape
    sizes = np.diff(qb)
    inv = np.zeros(q)
    for i in range(q):
        ql = np.sort(label[qb[i]:qb[i + 1]])[::-1][:trunc].astype(np.int64)
        mdcg = float((GAIN[ql] / np.log2(np.arange(len(ql)) + 2.0)).sum())
        inv[i] = 1.0 / mdcg if mdcg > 0 else 0.0
    gain = jnp.asarray(GAIN, jnp.float32)

    def one_query(s, lab, v, inv_max_dcg):
        s_in = jnp.where(v, s, -1e30)
        order = jnp.argsort(-s_in, stable=True)
        ss, sl, sv = s_in[order], lab[order], v[order]
        g = gain[jnp.clip(sl.astype(jnp.int32), 0, None)]
        ranks = jnp.arange(m)
        disc = 1.0 / jnp.log2(ranks + 2.0)
        n_valid = jnp.sum(sv.astype(jnp.int32))
        best, worst = ss[0], ss[jnp.maximum(n_valid - 1, 0)]
        iu, ju = ranks[:, None], ranks[None, :]
        pair = (iu < ju) & sv[:, None] & sv[None, :] & (sl[:, None] != sl[None, :]) & (iu < trunc)
        hi_is_i = sl[:, None] > sl[None, :]
        delta = jnp.where(hi_is_i, ss[:, None] - ss[None, :], ss[None, :] - ss[:, None])
        dn = jnp.abs(g[:, None] - g[None, :]) * jnp.abs(disc[:, None] - disc[None, :]) * \
            inv_max_dcg
        if norm:
            dn = jnp.where(best != worst, dn / (0.01 + jnp.abs(delta)), dn)
        p = 1.0 / (1.0 + jnp.exp(sig * delta))
        pl = jnp.where(pair, -sig * dn * p, 0.0)
        ph = jnp.where(pair, sig * sig * dn * p * (1.0 - p), 0.0)
        contrib = jnp.where(hi_is_i, 1.0, -1.0) * pl
        lam = jnp.sum(contrib, axis=1) - jnp.sum(contrib, axis=0)
        hes = jnp.sum(ph, axis=1) + jnp.sum(ph, axis=0)
        if norm:
            total = -2.0 * jnp.sum(pl)
            f = jnp.where(total > 0, jnp.log2(1.0 + total) / jnp.maximum(total, 1e-15), 1.0)
            lam, hes = lam * f, hes * f
        back = jnp.argsort(order, stable=True)
        return lam[back], hes[back]

    lam, hes = jax.vmap(one_query)(jnp.asarray(score)[idx], jnp.asarray(label)[idx],
                                   jnp.asarray(valid), jnp.asarray(inv, jnp.float32))
    n = len(score)
    g = np.zeros(n)
    h = np.zeros(n)
    g[idx[valid]] = np.asarray(lam)[valid]
    h[idx[valid]] = np.asarray(hes)[valid]
    assert sizes.sum() == n
    return g, h


def case(name, seed=0):
    """(sizes, labels, scores) of one layout case."""
    rng = np.random.default_rng(seed)
    if name == "heavy_tailed":
        sizes = np.clip(np.rint(rng.lognormal(np.log(60) - 0.5, 1.0, 40)), 1, 700)
        sizes = np.r_[sizes, 1, 29, 30, 31].astype(np.int64)
    elif name == "bucket_edges":
        sizes = np.array([7, 8, 9, 15, 16, 17, 31, 32, 33, 63, 64, 65, 127, 128, 129])
    elif name == "one_long_query":
        sizes = np.array([1, 2, 1000])
    elif name == "all_one_document":
        sizes = np.ones(12, np.int64)
    else:
        sizes = np.array([5, 30, 45, 300, 1])
    n = int(sizes.sum())
    labels = rng.integers(0, 5, n).astype(np.float32)
    scores = rng.standard_normal(n).astype(np.float32)
    qb = np.r_[0, np.cumsum(sizes)]
    if name == "ties_and_equal_labels":
        labels[qb[1]:qb[2]] = 3                       # a query of 30 all labelled 3
        scores[qb[2]:qb[3]] = np.round(scores[qb[2]:qb[3]])   # many tied scores
        scores[qb[3]:qb[4]] = 0.5                     # every score of a query tied
        scores[qb[3]] = -0.0
    return sizes, labels, scores


CASES = ["heavy_tailed", "bucket_edges", "one_long_query", "all_one_document",
         "ties_and_equal_labels"]


def objective(sizes, labels, **params):
    md = Metadata()
    md.set_label(labels)
    md.set_group(sizes)
    obj = LambdarankNDCG(Config({"objective": "lambdarank", **params}))
    obj.init(md, int(sizes.sum()))
    return obj


@pytest.mark.parametrize("trunc, norm", [(30, True), (30, False), (5, True)],
                         ids=["t30", "t30_no_norm", "t5"])
@pytest.mark.parametrize("name", CASES)
def test_bucketed_lambdas_equal_the_loop_and_the_full_pair_form(name, trunc, norm):
    sizes, labels, scores = case(name)
    qb = np.r_[0, np.cumsum(sizes)]
    obj = objective(sizes, labels, lambdarank_truncation_level=trunc, lambdarank_norm=norm)
    g, h = (np.asarray(a, np.float64) for a in obj.get_gradients(jnp.asarray(scores)))
    rl, rh = loop_lambdas(scores, labels, qb, trunc=trunc, norm=norm)
    scale = max(1e-6, np.abs(rl).max())
    np.testing.assert_allclose(g, rl, rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(h, rh, rtol=2e-5, atol=2e-6 * max(1e-6, np.abs(rh).max()))
    fg, fh = full_pair_lambdas(scores, labels, qb, trunc=trunc, norm=norm)
    np.testing.assert_allclose(g, fg, rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(h, fh, rtol=2e-5, atol=2e-6 * max(1e-6, np.abs(rh).max()))
    if name == "all_one_document":
        assert not g.any() and not h.any()


def test_chunked_pair_blocks_equal_the_whole_block(monkeypatch):
    """A bucket wider than one chunk of pair slots is cut along its queries,
    in whole lanes, the last chunk padded."""
    sizes, labels, scores = case("heavy_tailed")
    whole = [np.asarray(a) for a in objective(sizes, labels).get_gradients(jnp.asarray(scores))]
    monkeypatch.setattr(rank, "PAIR_CHUNK_ELEMENTS", 3000)
    monkeypatch.setattr(rank, "LANES", 2)
    cut = [np.asarray(a) for a in objective(sizes, labels).get_gradients(jnp.asarray(scores))]
    for a, b in zip(whole, cut):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("name", CASES)
def test_the_record_counts_slots_and_pairs_directly(name):
    sizes, labels, _ = case(name)
    rec = objective(sizes, labels).record()
    assert rec["queries"] == len(sizes) and rec["longest_query"] == sizes.max()
    width = bucket_width(sizes)
    assert all(w >= n for w, n in zip(width, sizes))
    assert rec["buckets"] == [[int(w), int((width == w).sum())] for w in np.unique(width)]
    assert rec["row_slots"] == int(width.sum())
    assert rec["pair_slots"] == int(sum(min(30, w) * w for w in width))
    direct = sum(1 for n in sizes for i in range(n) for j in range(i + 1, n) if i < 30)
    assert rec["pairs_counted"] == direct
    assert rec["pairs_counted"] <= rec["pair_slots"]


def test_every_row_has_one_slot():
    sizes, _, _ = case("heavy_tailed")
    qb = np.r_[0, np.cumsum(sizes)]
    lay = QueryLayout(qb, int(qb[-1]))
    flat_rows = np.concatenate([np.where(v, i, -1).ravel() for i, v in zip(lay.idx, lay.valid)])
    assert np.array_equal(flat_rows[lay.slot], np.arange(qb[-1]))
    assert sorted(lay.widths) == lay.widths and lay.widths[-1] == int(np.ceil(sizes.max() / 8) * 8)
    with pytest.raises(ValueError, match="rows"):
        QueryLayout(qb, int(qb[-1]) + 1)


def test_the_gradient_program_carries_its_scope():
    sizes, labels, scores = case("bucket_edges")
    obj = objective(sizes, labels)
    text = obj._grad_fn.lower(jnp.asarray(scores), *obj._bucket_args).as_text(
        debug_info=True)
    assert "lgbm.rank.lambdas" in text


def _rank_rows(seed=3, queries=30):
    rng = np.random.default_rng(seed)
    sizes = np.clip(np.rint(rng.lognormal(np.log(20), 0.8, queries)), 1, 120).astype(np.int64)
    n = int(sizes.sum())
    x = rng.standard_normal((n, 5))
    y = np.clip(np.rint(x[:, 0] + 0.5 * rng.standard_normal(n) + 1), 0, 4)
    return x, y, sizes


def test_block_input_with_groups_bins_as_the_whole_matrix():
    x, y, sizes = _rank_rows()
    params = {"objective": "lambdarank", "verbosity": -1, "min_data_in_bin": 1}
    whole = lgb.Dataset(x, y, group=sizes, params=params).construct()
    cuts = [0, 37, 200, len(x)]       # blocks that cut queries in two
    blocks = lgb.Dataset([x[a:b].astype(np.float32) for a, b in zip(cuts, cuts[1:])], y,
                         group=sizes, params=params).construct()
    f32 = lgb.Dataset(x.astype(np.float32), y, group=sizes, params=params).construct()
    assert np.array_equal(blocks.X_binned, f32.X_binned)
    assert np.array_equal(blocks.metadata.query_boundaries, whole.metadata.query_boundaries)
    assert np.array_equal(blocks.get_group(), sizes)
    with pytest.raises(ValueError, match="group sizes sum"):
        lgb.Dataset([x[:100], x[100:]], y, group=sizes[:-1], params=params).construct()


def test_lambdarank_trains_on_blocks_and_states_its_layout():
    x, y, sizes = _rank_rows()
    params = {"objective": "lambdarank", "verbosity": -1, "num_leaves": 7,
              "min_data_in_leaf": 1, "metric": "ndcg", "eval_at": [5]}
    blocks = [x[:300].astype(np.float32), x[300:].astype(np.float32)]
    bst = lgb.train(params, lgb.Dataset(blocks, y, group=sizes, params=params), 8)
    layout = bst.train_record.snapshot()["objective"]
    assert layout["name"] == "lambdarank" and layout["queries"] == len(sizes)
    assert layout["row_slots"] >= len(x)
    ref = lgb.train(params, lgb.Dataset(x.astype(np.float32), y, group=sizes, params=params), 8)
    np.testing.assert_allclose(bst.predict(x), ref.predict(x), rtol=1e-6, atol=1e-7)


def test_rank_xendcg_still_trains():
    x, y, sizes = _rank_rows(seed=5)
    params = {"objective": "rank_xendcg", "verbosity": -1, "num_leaves": 7,
              "min_data_in_leaf": 1}
    bst = lgb.train(params, lgb.Dataset(x, y, group=sizes, params=params), 6)
    p = bst.predict(x)
    assert np.isfinite(p).all() and p.std() > 0
    assert bst.train_record.snapshot()["objective"] == {}
    # the scores order documents with the labels within the queries
    qb = np.r_[0, np.cumsum(sizes)]
    agree = [np.corrcoef(p[a:b], y[a:b])[0, 1] for a, b in zip(qb, qb[1:])
             if b - a > 3 and np.ptp(y[a:b]) > 0 and np.ptp(p[a:b]) > 0]
    assert np.mean(agree) > 0.3
