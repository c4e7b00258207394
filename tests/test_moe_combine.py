"""The held experts' rows moved between token order and sorted-row order by
gathers through the inverse of the expert sort (``ops/moe.py``:
``_combine``, ``_token_rows``, ``_sum_of_pairs``) against the scatter-adds
they replace, and the static rule (``_gathers``) that picks one a bucket."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import moe as moe_ops

TOKENS, TOP_K, D, H, EXPERTS, HELD = 512, 4, 32, 24, 32, 4
N_PAIRS = TOKENS * TOP_K
BUCKETS = moe_ops.dropless_row_buckets(N_PAIRS, HELD, EXPERTS)   # 8, 384, 768, 2048


def routing(n_here, seed=0, empty=None):
    """``n_here`` of the pairs routed to the held experts (none to expert
    ``empty``), the rest elsewhere; sorted as the router sorts them."""
    rng = np.random.default_rng(seed)
    key = np.full(N_PAIRS, HELD)
    experts = [e for e in range(HELD) if e != empty]
    key[rng.choice(N_PAIRS, n_here, replace=False)] = rng.choice(experts, n_here)
    order = np.argsort(key, kind="stable").astype(np.int32)
    sizes = np.bincount(key, minlength=HELD + 1)[:HELD].astype(np.int32)
    return (jnp.asarray(order), jnp.asarray(np.argsort(order).astype(np.int32)),
            jnp.asarray(sizes), jnp.int32(n_here))


def weights(form, seed=1):
    rng = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
    return (w(TOKENS, D) * 5, jnp.asarray(rng.uniform(size=N_PAIRS), jnp.float32),
            w(HELD, D, H * (2 if form == "swiglu" else 1)), w(HELD, H, D))


def layer_on_rows(rows, form, route, path="xla", ratio=None, monkeypatch=None):
    """Value and gradients (tokens, gates, both weights) of one bucket."""
    if ratio is not None:
        monkeypatch.setattr(moe_ops, "GATHER_RATIO", ratio)
    order, inv, sizes, n_here = route
    weigh = jnp.cos(jnp.arange(TOKENS * D, dtype=jnp.float32)).reshape(TOKENS, D)

    def loss(xt, gate, w_in, w_down):
        y = moe_ops._experts_on_rows(rows, xt, order, gate, sizes, n_here, w_in, w_down, TOP_K,
                                     form, path)
        return (y * weigh).sum(), y
    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(
        *weights(form))
    return y, grads


def assert_same(got, want):
    (y, grads), (y_want, grads_want) = got, want
    np.testing.assert_allclose(y, y_want, rtol=1e-5, atol=1e-5)
    for g, g_want in zip(grads, grads_want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, g_want, rtol=1e-5, atol=2e-5)


# bucket rows, rows routed here: the smallest bucket, 1.5 x and 3 x the
# expected share, every pair
BUCKET_CASES = {"smallest": (8, 6), "1.5x": (384, 300), "3x": (768, 700),
                "every_pair": (2048, 1500)}


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("bucket", BUCKET_CASES)
def test_gathers_give_the_scatter_adds_value_and_gradients(bucket, form, monkeypatch):
    rows, n_here = BUCKET_CASES[bucket]
    assert rows in BUCKETS
    route = routing(n_here)
    assert_same(layer_on_rows(rows, form, route, ratio=1e9, monkeypatch=monkeypatch),
                layer_on_rows(rows, form, route, ratio=0, monkeypatch=monkeypatch))


@pytest.mark.parametrize("case,n_here,empty", [
    ("no_row_held", 0, None), ("every_row_held", 384, None), ("an_empty_expert", 300, 2)])
def test_gathers_at_the_edges_of_a_bucket(case, n_here, empty, monkeypatch):
    route = routing(n_here, seed=3, empty=empty)
    got = layer_on_rows(384, "swiglu", route, ratio=1e9, monkeypatch=monkeypatch)
    assert_same(got, layer_on_rows(384, "swiglu", route, ratio=0, monkeypatch=monkeypatch))
    if n_here == 0:
        assert not np.asarray(got[0]).any() and not any(np.asarray(g).any() for g in got[1])


def test_gathers_on_the_kernels_in_the_interpreter(monkeypatch):
    """The kernels leave the rows past ``n_here`` undefined (NaN in the
    interpreter): the gathers never read them into a token or its gradient."""
    route = routing(300, seed=4)
    assert_same(layer_on_rows(384, "relu2", route, "interpret", 1e9, monkeypatch),
                layer_on_rows(384, "relu2", route, "xla", 0, monkeypatch))


def test_rows_past_n_here_stay_out_of_tokens_and_gradients():
    """NaN planted in the rows past ``n_here``, in the combine's rows and in
    the row gather's cotangent: both helpers and their derivatives give what
    the same rows with zeros there give."""
    order, inv, sizes, n_here = routing(300, seed=5)
    rows, rng = 384, np.random.default_rng(6)
    clean = np.asarray(rng.standard_normal((rows, D)), np.float32)
    clean[300:] = 0
    planted = clean.copy()
    planted[300:] = np.nan
    xt, gate = weights("relu2")[:2]
    ct = jnp.asarray(rng.standard_normal((TOKENS, D)), jnp.float32)
    pair = order[:rows]

    def combine(ys):
        y, pull = jax.vjp(lambda ys, gate: moe_ops._combine(ys, gate, pair, inv, n_here,
                                                            TOP_K), ys, gate)
        return (y,) + pull(ct)

    def token_rows_grad(da):
        return jax.vjp(lambda x: moe_ops._token_rows(x, pair // TOP_K, inv, n_here, TOP_K),
                       xt)[1](da)[0]

    got = combine(jnp.asarray(planted)) + (token_rows_grad(jnp.asarray(planted)),)
    want = combine(jnp.asarray(clean)) + (token_rows_grad(jnp.asarray(clean)),)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(g, w)


def test_the_two_helpers_are_each_others_transpose():
    """``⟨x[token], r⟩ = ⟨x, Σ_j r[inv]⟩`` for rows ``r`` zero past ``n_here``."""
    order, inv, _, n_here = routing(300, seed=7)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((TOKENS, D)), jnp.float32)
    r = np.asarray(rng.standard_normal((384, D)), np.float32)
    r[300:] = 0
    token = order[:384] // TOP_K
    lhs = float((moe_ops._token_rows(x, token, inv, n_here, TOP_K) * r).sum())
    rhs = float((x * moe_ops._sum_of_pairs(jnp.asarray(r), inv, n_here, TOP_K)).sum())
    np.testing.assert_allclose(lhs, rhs, rtol=1e-5)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_the_whole_layer_gives_one_result_either_way(scoring, form, monkeypatch):
    """``moe_ffn_dropless`` end to end, its bucket chosen by the routing:
    value, rows routed and the gradients of the tokens, the router and both
    weights, gathers against scatter-adds."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, TOKENS // 2, D)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((EXPERTS, D)) * 0.3, jnp.float32)
    _, _, w_in, w_down = weights(form)

    def run(ratio):
        monkeypatch.setattr(moe_ops, "GATHER_RATIO", ratio)

        def loss(x, router, w_in, w_down):
            y, rows = moe_ops.moe_ffn_dropless(
                x, router, jnp.zeros((EXPERTS,)), w_in, w_down, num_experts=EXPERTS,
                top_k=TOP_K, first_expert=4, expert_form=form, scoring=scoring)[:2]
            return (y ** 2).sum(), rows
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)(
            x, router, w_in, w_down)

    (loss, rows), grads = run(1e9)
    (loss_s, rows_s), grads_s = run(0)
    assert float(rows) == float(rows_s) > 0
    np.testing.assert_allclose(float(loss), float(loss_s), rtol=1e-5)
    for g, g_s in zip(grads, grads_s):
        np.testing.assert_allclose(g, g_s, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("ratio", [0, 1, 5, 5.34, 256, 1e9])
def test_a_bucket_gathers_where_its_pairs_are_at_most_the_ratio_times_its_rows(
        ratio, monkeypatch):
    monkeypatch.setattr(moe_ops, "GATHER_RATIO", ratio)
    for rows in BUCKETS:
        assert moe_ops._gathers(N_PAIRS, rows, TOP_K) == (N_PAIRS / rows <= ratio)


@pytest.mark.parametrize("top_k,gathers", [(1, True), (4, True), (5, False), (6, False),
                                           (8, False)])
def test_a_bucket_gathers_only_where_a_token_has_few_choices(top_k, gathers):
    """The xing and lfm2 layers (4 a token) gather; keye's and solar's (8)
    and nemotron's (6) keep the scatter-adds in every bucket, whatever the
    ratio of pairs to rows."""
    assert moe_ops._gathers(4096 * top_k, 4096 * top_k, top_k) == gathers


@pytest.mark.parametrize("ratio,gathered", [(0, 0), (3, 2), (6, 3), (1e9, 4)])
def test_the_counters_count_each_bucket_of_a_traced_site_by_its_form(
        ratio, gathered, monkeypatch):
    """One count a bucket of a traced call site: 2048 / 768 / 384 / 8 rows over
    2,048 pairs are ratios 1, 2.7, 5.3 and 256."""
    monkeypatch.setattr(moe_ops, "GATHER_RATIO", ratio)
    x, router = jnp.zeros((TOKENS, D)), jnp.zeros((EXPERTS, D))
    _, _, w_in, w_down = weights("relu2")
    before = profiler.counters()
    jax.make_jaxpr(lambda x: moe_ops.moe_ffn_dropless(
        x, router, jnp.zeros((EXPERTS,)), w_in, w_down, num_experts=EXPERTS, top_k=TOP_K,
        expert_form="relu2")[0])(x)
    counted = {n: profiler.counters()[n] - before[n]
               for n in ("moe_combine_gather", "moe_combine_scatter")}
    assert counted == {"moe_combine_gather": gathered,
                       "moe_combine_scatter": len(BUCKETS) - gathered}
