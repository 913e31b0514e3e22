"""Per-architecture smoke tests (reduced configs, CPU).

For each assigned arch: instantiate the reduced same-family config, run
one forward/train step and a prefill→decode step, assert output shapes
and no NaNs.  Also checks param-count formulas against the real inits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config, get_smoke_config
from repro.models.registry import build_model

jax.config.update("jax_platform_name", "cpu")


def make_batch(cfg, b=2, s=64, rng=None):
    rng = np.random.default_rng(0) if rng is None else rng
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.vision_tokens, cfg.d_model)), jnp.bfloat16
        )
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.encoder_seq, cfg.d_model)), jnp.bfloat16
        )
    return batch


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_smoke_config(arch)
            model = build_model(cfg)
            params = model.init_params(jax.random.PRNGKey(0))
            cache[arch] = (cfg, model, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ASSIGNED)
def test_train_step_shapes_and_finite(arch, built):
    cfg, model, params = built(arch)
    batch = make_batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.train_loss(p, batch, remat=False), has_aux=True
    )(params)
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{arch}: loss={loss}"
    flat = jax.tree.leaves(grads)
    assert all(jnp.all(jnp.isfinite(g.astype(jnp.float32))) for g in flat), f"{arch}: NaN grads"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_prefill_then_decode(arch, built):
    cfg, model, params = built(arch)
    b, s = 2, 64
    batch = make_batch(cfg, b, s)
    logits, state = model.prefill(params, batch, remat=False)
    assert logits.shape == (b, cfg.padded_vocab)
    assert jnp.all(jnp.isfinite(logits.astype(jnp.float32))), f"{arch}: prefill NaN"

    next_tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)
    for _ in range(3):
        logits, state = model.decode_step(params, state, next_tok)
        assert logits.shape == (b, cfg.padded_vocab)
        assert jnp.all(jnp.isfinite(logits.astype(jnp.float32))), f"{arch}: decode NaN"
        next_tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ASSIGNED + ["mistral-large-123b"])
def test_full_config_exact_dims(arch):
    """The FULL configs carry the exact assigned dims (no allocation)."""
    cfg = get_config(arch)
    table = {
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "mamba2-780m": (48, 1536, 0, 0, 0, 50280),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
    }
    L, d, h, kv, ff, v = table[arch]
    assert cfg.num_layers == L and cfg.d_model == d and cfg.vocab_size == v
    assert cfg.num_heads == h and cfg.num_kv_heads == kv and cfg.d_ff == ff
    if arch == "granite-moe-3b-a800m":
        assert cfg.num_experts == 40 and cfg.experts_per_token == 8
    if arch == "llama4-maverick-400b-a17b":
        assert cfg.num_experts == 128 and cfg.experts_per_token == 1
    if arch == "mamba2-780m":
        assert cfg.ssm_state == 128
    if arch == "hymba-1.5b":
        assert cfg.ssm_state == 16


@pytest.mark.parametrize(
    "arch,lo,hi",
    [
        ("granite-34b", 32e9, 36e9),
        ("deepseek-67b", 64e9, 70e9),
        ("deepseek-coder-33b", 31e9, 35e9),
        ("yi-9b", 8.2e9, 9.5e9),
        ("whisper-large-v3", 1.4e9, 1.7e9),
        ("granite-moe-3b-a800m", 3.0e9, 3.6e9),
        ("llama4-maverick-400b-a17b", 385e9, 410e9),
        ("llava-next-mistral-7b", 6.7e9, 7.6e9),
        ("mamba2-780m", 0.72e9, 0.84e9),
        ("hymba-1.5b", 1.4e9, 1.7e9),
        ("mistral-large-123b", 118e9, 126e9),
    ],
)
def test_param_count_matches_public_size(arch, lo, hi):
    """The config formulas land at the model's public parameter count."""
    n = get_config(arch).param_count()
    assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9}, {hi/1e9}]"


def test_maverick_active_params_about_17b():
    cfg = get_config("llama4-maverick-400b-a17b")
    a = cfg.active_param_count()
    assert 15e9 <= a <= 19e9, f"active {a/1e9:.1f}B"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_param_count_formula_matches_init(arch, built):
    """param_count() (unpadded) vs actual init (padded vocab/experts):
    init must be >= formula and within the padding slack."""
    cfg, model, params = built(arch)
    actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    formula = cfg.param_count()
    pad_slack = (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model * 2 + 1_000_000
    if cfg.num_experts:
        mats = 3 if cfg.mlp_type == "swiglu" else 2
        pad_slack += (
            (cfg.padded_experts - cfg.num_experts)
            * (mats * cfg.d_model * cfg.d_ff + cfg.d_model)
            * (cfg.num_layers // cfg.moe_every)
        )
    assert formula * 0.85 <= actual <= formula + pad_slack, (
        f"{arch}: formula {formula} vs actual {actual} (slack {pad_slack})"
    )


def test_long_context_gate():
    """long_500k runs only for sub-quadratic archs (DESIGN.md §4)."""
    sub = {a for a in ASSIGNED if get_config(a).is_subquadratic}
    assert sub == {"mamba2-780m", "hymba-1.5b"}
