"""`model_math` against the program's own count and a hand count."""
import pytest

from benchmark import common, model_math


@pytest.mark.parametrize("name", ["mistral-7b-v0.3.serve", "mistral-7b-v0.3.train"])
def test_num_params_matches_the_program(name):
    from ray_tpu.models import llama

    cf = common.load_json(f"{common.BENCH_DIR}/configs/{name}.json")
    assert model_math.num_params(cf) == llama.num_params(common.llama_config(cf))


def test_hand_count_at_the_published_depth():
    cf = common.load_json(f"{common.BENCH_DIR}/configs/mistral-7b-v0.3.serve.json")
    full = {**cf, "num_hidden_layers": 32}
    attn = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096          # q, k+v, o
    mlp = 3 * 4096 * 14336
    assert model_math.layer_matmul_params(full) == attn + mlp == 218_103_808
    total = 2 * 32768 * 4096 + 32 * (attn + mlp + 2 * 4096) + 4096
    assert model_math.num_params(full) == total == 7_248_023_552  # Mistral-7B-v0.3's 7.25 B
    assert model_math.kv_bytes_per_token({**cf, "num_hidden_layers": 1}) == 4096  # 4 KB a layer


def test_flops_and_roofline():
    cf = common.load_json(f"{common.BENCH_DIR}/configs/mistral-7b-v0.3.train.json")
    L = cf["num_hidden_layers"]
    mm = L * 218_103_808 + 4096 * 32768
    assert model_math.matmul_params(cf) == mm
    assert model_math.train_flops_per_token(cf, 4096) == 6.0 * mm + 6.0 * L * 4096 * 4096
    # causal: half of what the program's default flops_per_token counts for attention
    from ray_tpu.models import llama

    full = llama.flops_per_token(common.llama_config(cf), 4096) - 6 * llama.num_params(common.llama_config(cf))
    assert model_math.train_flops_per_token(cf, 4096) - 6.0 * mm == full / 2
    # seven matrix products of 2*T*T*hd, halved, a head, a layer
    assert model_math.flash_step_flops(cf, 2, 4096) == L * 2 * 32 * 7 * 4096 * 4096 * 128
    peak = common.peaks_for("TPU v5 lite")
    r = model_math.roofline(197e12, 819e9 / 2, peak)
    assert r["bound"] == "compute" and r["least_s"] == pytest.approx(1.0)
    with pytest.raises(common.BenchFailure):
        common.peaks_for("TPU v9 imaginary")
