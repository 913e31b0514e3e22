"""A configuration is data: its ``model`` block reaches the program's
``ModelConfig`` field by field, whatever the family, and its reference
and counts are the modules of ``bench/`` that it names."""
import json

import numpy as np
import pytest

import smoke
import run

TINY_MOE = {"family": "moe", "num_layers": 2, "d_model": 64, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 16, "d_ff": 32, "vocab_size": 256,
            "mlp_type": "swiglu", "num_experts": 8, "experts_per_token": 2,
            "moe_shared_expert": True, "capacity_factor": 2.0, "rope_theta": 500000.0,
            "norm_eps": 1e-06, "context_length": 1024}

REFERENCE = '''
CALLS = []


def logits(params, sizes, seq, rows, precision="float32"):
    import numpy as np
    CALLS.append((len(seq), tuple(rows), precision))
    out = np.zeros((len(rows), sizes["vocab_size"]), np.float32)
    out[:, 7] = 2.0 if precision == "float32" else 1.0
    return out
'''

COUNTS = '''
MARK = "test-local counts"
'''


def _root(tmp_path, model, extra=None):
    """A checkout holding one configuration, its traffic and two
    test-local modules."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "local_ref.py").write_text(REFERENCE)
    (tmp_path / "bench" / "local_counts.py").write_text(COUNTS)
    config = {"model": model, "reference": "local_ref", "counts": "local_counts",
              **(extra or {})}
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (tmp_path / "bench" / "traffic" / "t.json").write_text(json.dumps(smoke.TRAFFIC))
    bench = {"configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
             "workloads": [{"name": "tiny.t", "config": "tiny", "traffic": "t",
                            "chips": 1}],
             "end_to_end": [], "per_layer": [{"name": "decode.compiles"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return run.load_spec("tiny.t", tmp_path)


def test_a_new_family_is_built_from_its_file_alone(tmp_path):
    spec = _root(tmp_path, TINY_MOE)
    cfg = run.model_config(spec.cell["config"], spec.sizes)
    assert cfg.name == "tiny" and cfg.family == "moe"
    for key, value in TINY_MOE.items():
        if key not in run.HARNESS_KEYS:
            assert getattr(cfg, key) == value, key
    from repro.models.registry import build_model

    assert build_model(cfg).cfg is cfg


def test_lists_become_tuples_so_the_config_hashes():
    cfg = run.model_config("x", {**smoke.MODELS["yi"], "sliding_window": [4, 8]})
    assert cfg.sliding_window == (4, 8)
    hash(cfg)


@pytest.mark.parametrize("key", ["num_expertz", "mlp"])
def test_an_unknown_model_key_is_refused_by_name(tmp_path, key):
    spec = _root(tmp_path, {**TINY_MOE, key: 1})
    with pytest.raises(run.BenchError, match=key):
        run.model_config(spec.cell["config"], spec.sizes)


def test_the_served_configuration_names_reference_and_flops():
    spec = run.load_spec("yi9b.docqa")
    assert spec.config["reference"] == "reference" and spec.config["counts"] == "flops"
    assert spec.reference.__file__ == str(run.BENCH / "reference.py")
    assert spec.counts.__file__ == str(run.BENCH / "flops.py")
    assert spec.reference is run.load_spec("yi9b.docqa").reference  # loaded once
    cfg = run.model_config(spec.cell["config"], spec.sizes)
    assert (cfg.family, cfg.mlp_type, cfg.num_layers) == ("dense", "swiglu", 24)


def test_a_missing_module_is_refused(tmp_path):
    spec = _root(tmp_path, TINY_MOE, {"reference": "no_such_reference"})
    with pytest.raises(run.BenchError, match="no_such_reference"):
        spec.reference


def test_the_check_reads_the_named_reference(tmp_path):
    spec = _root(tmp_path, TINY_MOE)
    r = run.Run(spec, 1, 1.0, False, require_tpu=False, control=True)
    r.params, r.prompts = None, {0: np.arange(5, dtype=np.int32)}
    served = np.array([7, 7, 3])
    d = r.gaps_of(0, served)
    # the local reference puts token 7 first by 2.0: served 3 lies 2.0 below
    assert d["program_gap"] == 2.0 and d["control_gap"] == 0.0
    assert spec.reference.CALLS == [(7, (4, 5, 6), "float32"), (7, (4, 5, 6), "int8")]


def test_per_layer_readers_get_the_named_counts(tmp_path, monkeypatch):
    spec = _root(tmp_path, TINY_MOE)
    r = run.Run(spec, 1, 1.0, True, require_tpu=False)
    r.tracer, r.calls, r.device_trace = None, {"prefill": [], "decode": []}, None
    r.window_compiles, r.trace_t0, r.trace_t1 = {"jit_decode_step": 0}, 0.0, 1.0

    class Reader:
        @staticmethod
        def read(ctx):
            return ctx.flops.MARK

    monkeypatch.setattr(run, "_reader", lambda name: Reader)
    assert r.per_layer([]) == {"decode.compiles": "test-local counts"}


def test_warm_up_zeros_only_the_large_leaves_on_the_device(monkeypatch):
    """Decode states come from ``decode_state_shape``: leaves up to
    ``HOST_ZEROS_BYTES`` are put from the host, so the warm-up makes one
    device zero per (batch, pages) shape, shared by k and v."""
    import jax.numpy as jnp

    made = []
    zeros = jnp.zeros

    def spy(shape, dtype=None, **kw):
        if "device" in kw:
            made.append(tuple(shape))
        return zeros(shape, dtype, **kw)

    monkeypatch.setattr(jnp, "zeros", spy)
    monkeypatch.setattr(run, "HOST_ZEROS_BYTES", 1024)  # over a block table, under k
    r = run.Run(smoke.spec("yi"), 3, 1.0, False, require_tpu=False)
    r.build()
    r.warm()
    shapes = r.decode_shapes()
    assert len(made) == len(shapes) == len(set(made))
    assert {s[1:3] for s in made} == set(shapes)  # (batch, pages) of k and v
