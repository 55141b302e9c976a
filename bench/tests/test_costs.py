"""Operation and byte counts of the dense GQA architecture module against
hand counts for both configurations."""
import pytest
from conftest import BENCH

from harness import spec

QWEN, GLM = "qwen2.5-3b-w8a8", "chatglm3-6b-w8a8"
# both configurations name the dense GQA block
costs = spec.architecture(spec.load_cell("qwen2.5-3b.chat"))


def shapes(name):
    config = spec.read_json(BENCH / "configs" / f"{name}.json")
    assert config["reference"] == "dense_gqa"
    return costs.Shapes.of(config)


def test_projection_params_by_hand():
    # qwen2.5-3B: q 2048x2048, k and v 2048x256, o 2048x2048, 3 x 2048x11008
    assert costs.projection_params(shapes(QWEN)) == (
        2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048 + 3 * 2048 * 11008)
    # chatglm3-6B: q 4096x4096, k and v 4096x256, o, 3 x 4096x13696
    assert costs.projection_params(shapes(GLM)) == (
        4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096 + 3 * 4096 * 13696)
    assert 36 * costs.projection_params(shapes(QWEN)) == 2_774_532_096
    assert 28 * costs.projection_params(shapes(GLM)) == 5_710_544_896


@pytest.mark.parametrize("name", [QWEN, GLM])
def test_prefill_and_decode_ops_by_hand(name):
    s = shapes(name)
    p, L, hq = costs.projection_params(s), s.layers, s.n_heads * s.head_dim
    head = 2 * s.vocab * s.d_model
    # a 3-token prompt: contexts 1, 2, 3; one LM-head row
    assert costs.prefill_ops(s, 0, 3) == L * (2 * p * 3 + 4 * hq * 6) + head
    # its suffix after 1 committed token: contexts 2, 3
    assert costs.prefill_ops(s, 1, 3) == L * (2 * p * 2 + 4 * hq * 5) + head
    assert costs.decode_ops(s, 100) == L * (2 * p + 4 * hq * 100) + head
    # prefill of n equals n tokens' forwards plus one head row
    assert costs.prefill_ops(s, 0, 50) == sum(
        costs.token_ops(s, c) for c in range(1, 51)) + head


def test_bytes_by_hand():
    q = shapes(QWEN)
    chans = 2048 + 2 * 256 + 2048 + 2 * 11008 + 2048
    per_layer_q = (costs.projection_params(q) + 4 * chans
                   + 4 * (2048 + 2 * 256) + 4 * 2 * 2048)
    assert costs.weight_bytes(q) == (36 * per_layer_q + 4 * 2048
                                     + 2 * 151936 * 2048)
    # tied: the embedding is the LM head, read whole by every tick
    assert costs.decode_weight_bytes(q) == costs.weight_bytes(q)
    assert costs.kv_bytes_per_token(q) == 36_864
    assert costs.decode_tick_bytes(q, [10, 20]) == (
        costs.weight_bytes(q) + 36_864 * 30)
    g = shapes(GLM)
    assert costs.kv_bytes_per_token(g) == 28_672
    # untied: the embedding's rows are gathered, the LM head read whole
    assert costs.weight_bytes(g) - costs.decode_weight_bytes(g) == (
        2 * 65024 * 4096)
