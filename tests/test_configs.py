"""Assigned-architecture configs: exact published numbers + smoke reduction."""
import pytest

from repro.configs import ARCHS, SHAPES, get, get_smoke, shape_applicable

# (arch, layers, d_model, heads, kv_heads, d_ff, vocab) from the assignment
ASSIGNED = {
    "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
    "yi-6b": (32, 4096, 32, 4, 11008, 64000),
    "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
    "gemma-7b": (28, 3072, 16, 16, 24576, 256000),
    "mamba2-130m": (24, 768, 0, 0, 0, 50280),
    "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
    "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
    "moonshot-v1-16b-a3b": (48, 2048, 16, 16, 1408, 163840),
    "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
    "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
}


def test_all_archs_registered():
    assert set(ARCHS) == set(ASSIGNED)


@pytest.mark.parametrize("arch", list(ASSIGNED))
def test_exact_published_numbers(arch):
    cfg = get(arch)
    L, d, h, kv, ff, v = ASSIGNED[arch]
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.vocab_size == v
    if h:
        assert cfg.n_heads == h and cfg.n_kv_heads == kv
    assert cfg.d_ff == ff


def test_family_extras():
    assert get("mamba2-130m").family == "ssm"
    assert get("mamba2-130m").ssm_state == 128
    assert get("zamba2-2.7b").family == "hybrid"
    assert get("zamba2-2.7b").ssm_state == 64
    assert get("moonshot-v1-16b-a3b").n_experts == 64
    assert get("moonshot-v1-16b-a3b").experts_per_token == 6
    assert get("dbrx-132b").n_experts == 16
    assert get("dbrx-132b").experts_per_token == 4
    assert get("gemma-7b").resolved_head_dim() == 256
    assert get("gemma-7b").mlp == "geglu"
    assert get("nemotron-4-15b").mlp == "relu2"
    assert get("seamless-m4t-medium").family == "encdec"
    assert get("seamless-m4t-medium").enc_layers > 0
    assert get("internvl2-2b").family == "vlm"


@pytest.mark.parametrize("arch", list(ASSIGNED))
def test_smoke_is_same_family_but_small(arch):
    full, smoke = get(arch), get_smoke(arch)
    assert smoke.family == full.family
    assert smoke.n_layers < full.n_layers
    assert smoke.d_model < full.d_model
    assert smoke.vocab_size < full.vocab_size
    if full.family == "moe":
        assert 0 < smoke.n_experts <= full.n_experts
        assert smoke.experts_per_token <= smoke.n_experts


def test_shapes_assignment():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1


def test_long_context_applicability():
    ok, _ = shape_applicable(get("mamba2-130m"), SHAPES["long_500k"])
    assert ok
    ok, _ = shape_applicable(get("zamba2-2.7b"), SHAPES["long_500k"])
    assert ok
    for arch in ("tinyllama-1.1b", "gemma-7b", "dbrx-132b"):
        ok, why = shape_applicable(get(arch), SHAPES["long_500k"])
        assert not ok and "sub-quadratic" in why


def test_param_counts_near_published():
    # sanity: 6N within a factor-of-2 band of the published sizes
    expect = {"tinyllama-1.1b": 1.1e9, "yi-6b": 6e9, "gemma-7b": 8.5e9,
              "nemotron-4-15b": 15e9, "mamba2-130m": 130e6,
              "dbrx-132b": 132e9, "zamba2-2.7b": 2.7e9}
    for arch, n in expect.items():
        got = get(arch).param_count()
        assert 0.5 * n < got < 2.2 * n, (arch, got, n)


@pytest.mark.parametrize("cfg", [get_smoke("zamba2-2.7b"), get("zamba2-2.7b")],
                         ids=["smoke", "published"])
def test_hybrid_param_count_matches_the_specs(cfg):
    """Each shared block once, an adapter and a linear per hybrid layer."""
    from repro.models.api import build_model
    assert cfg.param_count() == build_model(cfg).param_count()


def test_zamba2_published_total():
    # 54 Mamba2 layers, two shared blocks, nine adapters + linears, the
    # tied embedding: 2.66 B
    assert get("zamba2-2.7b").param_count() == 2_662_214_560
