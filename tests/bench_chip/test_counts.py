"""Operation and byte counts of the served models, against sums worked
by hand from the published shapes."""
import dataclasses
import json

from conftest import ROOT

from benchmarks.chip import counts

CONFIGS = ROOT / "benchmarks" / "chip" / "configs"


def _dims(name: str, layers: int = 0) -> counts.Dims:
    m = counts.Dims.of(json.loads((CONFIGS / f"{name}.json").read_text()))
    return dataclasses.replace(m, layers=layers) if layers else m


def test_parameter_totals_match_the_published_models():
    # Qwen2.5-3B: 3.086 B (tied embeddings, q/k/v biases)
    assert counts.n_params(_dims("qwen2.5-3b")) == 3_085_938_688
    # Yi-6B at its published 32 layers: 6.06 B (untied)
    assert counts.n_params(_dims("yi-6b", layers=32)) == 6_061_035_520


def test_kv_bytes_per_token():
    # 2 (k, v) x layers x kv heads x 128 x 2 bytes
    assert counts.kv_bytes_per_token(_dims("qwen2.5-3b")) == 36_864
    assert counts.kv_bytes_per_token(_dims("yi-6b")) == 65_536


def test_prefill_count_by_hand():
    # Qwen2.5-3B, a 32-token prompt in one chunk, its last token's logits.
    # matmul weights per layer: q,o 2*2048*2048 + k,v 2*2048*256
    # + gate,up,down 3*2048*11008 = 77_070_336
    matmul = 2 * 36 * 77_070_336 * 32
    attn = 4 * 36 * 16 * 128 * sum(p + 1 for p in range(32))
    head = 2 * 2048 * 151_936
    assert matmul + attn + head == 178_348_097_536
    got = counts.prefill_flops(_dims("qwen2.5-3b"), 0, 32, last=True)
    assert got == 178_348_097_536
    # a later chunk attends further back and chooses no token
    mid = counts.prefill_flops(_dims("qwen2.5-3b"), 32, 64, last=False)
    assert mid == 2 * 36 * 77_070_336 * 32 + 4 * 36 * 16 * 128 * sum(
        p + 1 for p in range(32, 64))


def test_decode_count_by_hand():
    # Yi-6B at 32 layers, one token attending over 1000 positions.
    # matmul weights per layer: q,o 2*4096*4096 + k,v 2*4096*512
    # + 3*4096*11008 = 173_015_040
    m = _dims("yi-6b", layers=32)
    flops = 2 * 32 * 173_015_040 + 4 * 32 * 32 * 128 * 1000 + 2 * 4096 * 64_000
    assert counts.decode_flops(m, 1000) == flops == 12_121_538_560
    # one step over two sequences attending over 1000 and 2000 positions:
    # every weight once (layers with norms, final norm, the head), the two
    # live KVs, and one new KV row each
    weights = 2 * (32 * (173_015_040 + 2 * 4096) + 4096 + 64_000 * 4096)
    assert weights == 11_597_783_040
    assert counts.decode_step_bytes(m, [1000, 2000]) == (
        weights + (3000 + 2) * 65_536) == 11_794_522_112
