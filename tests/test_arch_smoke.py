"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU; asserts shapes and no NaNs. Full configs are
exercised only via the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config, list_configs
from repro.launch.train import TrainConfig, init_state, make_train_step
from repro.models.model import apply_model, init_cache, init_model, lm_loss

ARCHS = list_configs()


def _data(cfg, rng, b=2, s=16):
    tok = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    enc = (jax.random.normal(rng, (b, cfg.encoder_seq, cfg.d_model))
           if cfg.encoder_decoder else None)
    return tok, enc


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg = get_config(arch).reduced()
    rng = jax.random.PRNGKey(0)
    params = init_model(rng, cfg, max_pos=64)
    tok, enc = _data(cfg, rng)
    logits, aux, _ = apply_model(params, tok, cfg, mode="train",
                                 enc_embed=enc)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux["balance"]))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_updates_and_finite(arch):
    cfg = get_config(arch).reduced()
    tc = TrainConfig(lr=1e-3, remat_policy="none")
    rng = jax.random.PRNGKey(1)
    state = init_state(rng, cfg, tc, max_pos=64)
    tok, enc = _data(cfg, rng)
    batch = {"tokens": tok, "targets": tok,
             "weights": jnp.ones(tok.shape, jnp.float32)}
    if enc is not None:
        batch["enc_embed"] = enc
    step = jax.jit(make_train_step(cfg, tc))
    new_state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    assert int(new_state["step"]) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        state["params"], new_state["params"])
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_full_forward(arch):
    cfg = get_config(arch).reduced()     # MoE: drop-free, so exact
    rng = jax.random.PRNGKey(2)
    params = init_model(rng, cfg, max_pos=64)
    b, s = 2, 16
    tok, enc = _data(cfg, rng, b, s)
    full, _, _ = apply_model(params, tok, cfg, mode="train", enc_embed=enc)
    _, _, cache = apply_model(params, tok[:, :s - 1], cfg, mode="prefill",
                              enc_embed=enc)
    cache = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, 1)]
                          + [(0, 0)] * (c.ndim - 3))
        if c.ndim >= 3 and c.shape[2] == s - 1 else c, cache)
    step, _, _ = apply_model(params, tok[:, s - 1:], cfg, mode="decode",
                             cache=cache, cache_index=jnp.int32(s - 1))
    err = float(jnp.max(jnp.abs(step[:, 0] - full[:, -1])))
    assert err < 2e-4, f"decode/train mismatch {err}"


def _masked_loss(cfg, enc):
    def loss(p, t, w):
        lg, aux, _ = apply_model(p, t, cfg, mode="train", enc_embed=enc,
                                 loss_weights=w)
        return lm_loss(lg, t, w, aux["balance"])
    return loss


def _max_diff(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))),
        a, b)))


@pytest.mark.parametrize("arch", ARCHS)
def test_masked_weights_equal_subset_gradients(arch):
    """Algorithm-1 semantics of the masked fast path: zeroing an agent's
    loss weights gives exactly the gradient of the surviving examples.

    MoE archs too, with the balance loss on: routing is drop-free, so no
    row takes another's expert capacity, and the sequence-wise balance
    loss is averaged over rows by their weights (DESIGN.md §5)."""
    cfg = get_config(arch).reduced()
    rng = jax.random.PRNGKey(3)
    params = init_model(rng, cfg, max_pos=64)
    tok, enc = _data(cfg, rng, b=4, s=8)
    w_mask = jnp.concatenate([jnp.zeros((2, 8)), jnp.ones((2, 8))])
    g_masked = jax.grad(_masked_loss(cfg, enc))(params, tok, w_mask)
    g_subset = jax.grad(_masked_loss(cfg, enc[2:] if enc is not None
                                     else None))(params, tok[2:],
                                                 jnp.ones((2, 8)))
    assert _max_diff(g_masked, g_subset) < 2e-5


@pytest.mark.parametrize("arch", ARCHS)
def test_masked_rows_do_not_move_gradients(arch):
    """The masked rows' tokens replaced by others: the masked-path
    gradient does not change, to f32 rounding (MoE: not through the
    router, the balance loss or the experts' buffers)."""
    cfg = get_config(arch).reduced()
    rng = jax.random.PRNGKey(4)
    params = init_model(rng, cfg, max_pos=64)
    tok, enc = _data(cfg, rng, b=4, s=8)
    other = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                               cfg.vocab_size)
    w_mask = jnp.concatenate([jnp.zeros((2, 8)), jnp.ones((2, 8))])
    loss = _masked_loss(cfg, enc)
    g = jax.grad(loss)(params, tok, w_mask)
    g_other = jax.grad(loss)(params, tok.at[:2].set(other), w_mask)
    assert _max_diff(g, g_other) < 2e-5
