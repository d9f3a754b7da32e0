"""The traffic generator: lengths in range, the same waves for a seed,
the same sizes in the same order for another seed and in every wave,
documents shared."""
import json

from conftest import ROOT

from benchmarks.chip import traffic

MIXES = ROOT / "benchmarks" / "chip" / "traffic"
BIG_SEED = 2**31 + 12345


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _in(dist, n):
    return dist["min"] <= n <= dist["max"]


def test_lengths_in_range_for_every_mix():
    for name in ("longctx", "docqa", "chat", "docbatch"):
        mix = _mix(name)
        for index in range(4):
            w = traffic.wave(mix, 1000, BIG_SEED, index)
            assert len(w.prompts) == mix["wave"]
            assert w.max_new_tokens == mix["output"]
            for p, d in zip(w.prompts, w.doc_of):
                assert all(1 <= t < 1000 for t in p)
                if d < 0:
                    assert _in(mix["prompt"], len(p))
                else:
                    doc = len(p) - min(mix["prompt"]["max"], len(p))
                    assert len(p) <= mix["docs"]["max"] + mix["prompt"]["max"]
                    assert doc <= mix["docs"]["max"]


def test_same_seed_same_waves():
    mix = _mix("chat")
    a = traffic.wave(mix, 151936, BIG_SEED, 1)
    b = traffic.wave(mix, 151936, BIG_SEED, 1)
    assert a.prompts == b.prompts and a.max_new_tokens == b.max_new_tokens


def test_other_seed_same_sizes_same_order():
    for name in ("longctx", "docqa", "chat", "docbatch"):
        mix = _mix(name)
        a = traffic.wave(mix, 1000, 7, 0)
        b = traffic.wave(mix, 1000, BIG_SEED, 0)
        assert list(map(len, a.prompts)) == list(map(len, b.prompts))
        assert a.doc_of == b.doc_of
        assert a.max_new_tokens == b.max_new_tokens
        assert a.prompts != b.prompts
        # a later wave holds the same sizes, in another order
        c = traffic.wave(mix, 1000, 7, 3)
        assert sorted(map(len, c.prompts)) == sorted(map(len, a.prompts))
        assert c.max_new_tokens == a.max_new_tokens
    # the warm-up stream never repeats a measured wave
    w = traffic.wave(_mix("chat"), 1000, 7, 0, stream=traffic.WARMUP)
    assert w.prompts != traffic.wave(_mix("chat"), 1000, 7, 0).prompts


def test_docqa_shares_four_documents_across_sixteen_requests():
    mix = _mix("docqa")
    w = traffic.wave(mix, 64000, BIG_SEED, 0)
    assert len(w.prompts) == 16
    assert sorted(w.doc_of) == [d for d in range(4) for _ in range(4)]
    for d in range(4):
        group = [p for p, k in zip(w.prompts, w.doc_of) if k == d]
        short = min(len(p) for p in group)
        doc_len = short - mix["prompt"]["max"]
        head = group[0][:doc_len]
        assert doc_len >= mix["docs"]["min"] - mix["prompt"]["max"]
        assert all(p[:doc_len] == head for p in group)
    # different documents differ
    firsts = {tuple(p[:64]) for p in w.prompts}
    assert len(firsts) == 4


def test_every_cell_stays_within_its_model_and_slots():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: ROOT / c["file"] for c in bench["configs"]}
    for wl in bench["workloads"]:
        cfg = json.loads(files[wl["config"]].read_text())
        cell = json.loads((ROOT / "benchmarks" / "chip" / "cells"
                           / f"{wl['name']}.json").read_text())
        w = traffic.wave(_mix(wl["traffic"]), cfg["vocab_size"], BIG_SEED, 0)
        longest = max(map(len, w.prompts)) + w.max_new_tokens
        assert longest <= cfg["max_position_embeddings"], wl["name"]
        assert longest <= cell["max_len"], wl["name"]
        assert len(w.prompts) <= cell["max_batch"], wl["name"]


def test_docbatch_stays_within_yi6b_positions_with_the_same_sizes():
    mix = _mix("docbatch")
    want = None
    for seed in (0, 7, BIG_SEED, 2**40 + 1):
        w = traffic.wave(mix, 64000, seed, 0)
        assert set(w.doc_of) == {-1}            # nothing shared
        sizes = list(map(len, w.prompts))
        assert len(sizes) == 12 and w.max_new_tokens == 256
        # yi-6b publishes 4096 positions: the longest prompt and its output
        assert max(sizes) + w.max_new_tokens <= 4096
        assert min(sizes) >= 2048
        want = want or sizes
        assert sizes == want
    assert sum(want) == 34_206
