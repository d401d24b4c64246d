"""The dropless expert-share layer (``models/moe.expert_share_block``) and
its grouped matmul on the CPU, at tiny sizes with seeded weights.

A layer is cut into shares the way expert parallelism cuts it: each
share holds a run of the router's experts and computes their part of the
result.  Float32 compute, so the sums below agree to float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models import moe as M

R, K, D, F = 8, 2, 64, 32         # router width, experts a token, widths


def _cfg(held=R, first=0):
    return ModelConfig(name="share", family="moe", num_layers=1, d_model=D,
                       num_heads=4, num_kv_heads=2, d_ff=F, vocab_size=64,
                       num_experts=held, num_experts_per_tok=K,
                       moe_router_width=R, moe_first_expert=first,
                       dtype="float32")


@pytest.fixture(scope="module")
def uncut():
    params = M.init_moe(jax.random.PRNGKey(3), _cfg())
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, D), jnp.float32)
    return params, x


def _share(params, first, held):
    return dict(params, **{k: params[k][first:first + held]
                           for k in ("wi", "wg", "wo")})


def _dense_mixture(params, cfg, x):
    """Every held expert on every token, weighted by its renormalised
    gate where the router picked it."""
    gate, eidx = M.route(params, cfg, x.reshape(-1, D))
    local = eidx - cfg.moe_first_expert
    w = jnp.sum(jnp.where(local[..., None] == jnp.arange(cfg.num_experts),
                          gate[..., None], 0.0), axis=1)      # (T, E)
    xf = x.reshape(-1, D)
    with jax.default_matmul_precision("highest"):
        h = (jax.nn.silu(jnp.einsum("td,edf->etf", xf, params["wg"]))
             * jnp.einsum("td,edf->etf", xf, params["wi"]))
        y = jnp.einsum("etf,efd->etd", h, params["wo"])
    return jnp.einsum("te,etd->td", w, y).reshape(x.shape)


def test_shares_add_up_to_the_uncut_layer(uncut):
    params, x = uncut
    valid = jnp.ones(x.shape[:2], bool)
    whole, counts = M.expert_share_block(params, _cfg(), x, valid)
    parts = [M.expert_share_block(_share(params, f, 2), _cfg(2, f), x, valid)
             for f in range(0, R, 2)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole,
                               rtol=1e-5, atol=1e-5)
    # every assignment lands in exactly one share
    assert int(sum(p[1]["rows"].sum() for p in parts)) == x.shape[0] * \
        x.shape[1] * K == int(counts["rows"].sum())


def test_token_output_is_independent_of_its_batch(uncut):
    """A token served alone equals the same token beside other tokens and
    padding rows: nothing a neighbour routes can push it out."""
    params, x = uncut
    cfg = _cfg(4, 2)
    p = _share(params, 2, 4)
    crowd = jnp.concatenate([x] * 30, axis=1)             # (3, 150, D)
    valid = jnp.ones(crowd.shape[:2], bool).at[:, 140:].set(False)
    many, _ = M.expert_share_block(p, cfg, crowd, valid)
    for b, s in [(0, 0), (1, 3), (2, 4)]:
        alone, _ = M.expert_share_block(p, cfg, x[b:b + 1, s:s + 1],
                                        jnp.ones((1, 1), bool))
        np.testing.assert_allclose(many[b, s], alone[0, 0], rtol=1e-6,
                                   atol=1e-6)


def test_invalid_rows_get_no_expert_rows(uncut):
    params, x = uncut
    cfg = _cfg()
    valid = jnp.zeros(x.shape[:2], bool).at[0, :2].set(True)
    out, counts = M.expert_share_block(params, cfg, x, valid)
    assert int(counts["rows"].sum()) == 2 * K
    # every group with a row visits the one row tile those rows share
    hit = int((counts["rows"] > 0).sum())
    assert int(counts["executed"]) == M.ROW_TILE * hit
    assert not np.asarray(out[0, 2:]).any() and not np.asarray(out[1:]).any()
    none, counts = M.expert_share_block(params, cfg, x,
                                        jnp.zeros(x.shape[:2], bool))
    assert int(counts["rows"].sum()) == 0 == int(counts["executed"])
    assert not np.asarray(none).any()


@pytest.mark.parametrize("first,held", [(0, R), (2, 4)])
def test_dropless_layer_equals_dense_mixture_of_held_experts(uncut, first,
                                                             held):
    params, x = uncut
    cfg = _cfg(held, first)
    p = _share(params, first, held)
    got, counts = M.expert_share_block(p, cfg, x, jnp.ones(x.shape[:2],
                                                           bool))
    np.testing.assert_allclose(got, _dense_mixture(p, cfg, x), rtol=1e-5,
                               atol=1e-5)
    assert int(counts["executed"]) >= int(counts["rows"].sum()) > 0


def test_skewed_routing_drops_nothing(uncut):
    """Every token routed to the same two experts: a capacity bound would
    drop most of them, the dropless layer keeps all."""
    params, x = uncut
    cfg = _cfg()
    skew = dict(params, router={"w": jnp.zeros((D, R)).at[:, :2].set(
        jnp.abs(x).mean() * 50.0)})
    xs = jnp.abs(x)                                        # logits > 0
    got, counts = M.expert_share_block(skew, cfg, xs, jnp.ones(x.shape[:2],
                                                               bool))
    assert counts["rows"].tolist() == [15, 15, 0, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(got, _dense_mixture(skew, cfg, xs),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sizes,tm,layer", [
    ([3, 0, 17, 5], 8, 0), ([0, 0, 0, 0], 8, 0), ([16, 16, 0, 0], 8, 0),
    ([40, 0, 0, 1], 16, 0), ([1, 1, 1, 1], 32, 0), ([3, 0, 17, 5], 8, 2)])
def test_expert_gmm_matches_ref(sizes, tm, layer):
    """The grouped matmul against the per-row reference, for groups that
    share a row tile, fill whole tiles, are empty or leave every group
    empty, and for one layer's groups read from three layers' stacked
    weights; ``executed`` counts tm rows a (group, row tile) visit."""
    rng = np.random.default_rng(0)
    G, m, W = len(sizes), 64, 3 * len(sizes)
    wi, wg = (jnp.asarray(rng.normal(size=(W, D, F)) / 8, jnp.float32)
              for _ in range(2))
    wo = jnp.asarray(rng.normal(size=(W, F, D)) / 6, jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, D)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    first = jnp.int32(layer * G)
    out, executed = ops.expert_gmm(x, wi, wg, wo, gs, first, tm=tm)
    n = sum(sizes)
    np.testing.assert_allclose(out[:n], ref.expert_gmm_ref(x, wi, wg, wo,
                                                           gs, first)[:n],
                               rtol=1e-5, atol=1e-5)
    ends = np.cumsum(sizes)
    visits = sum((e - 1) // tm - (e - s) // tm + 1
                 for s, e in zip(sizes, ends) if s)
    assert int(executed) == tm * visits
