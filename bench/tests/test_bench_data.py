"""The benchmark's data files, shape arithmetic and BENCHMARK.json."""
import json
import re
from collections import Counter

import pytest

from bench import flops, traffic_gen
from bench.tests.conftest import DATA, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
CONFIGS = SPEC["configs"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")

# configuration key -> the source's config.json key
SOURCE_KEY = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim", "d_ff": "intermediate_size", "vocab": "vocab_size",
    "tie_embeddings": "tie_word_embeddings", "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps",
}
WIDTHS = {"d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab"}


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_traffic_is_seeded_and_keeps_its_length_counts(name):
    mix = _mix(name)
    seed = 2**31 + 12345
    a = traffic_gen.requests(mix, seed, vocab=151936)
    assert a == traffic_gen.requests(mix, seed, vocab=151936)
    b = traffic_gen.requests(mix, seed + 1, vocab=151936)
    assert [p for _, p, _ in a] != [p for _, p, _ in b]
    want = Counter({int(n): int(c) for n, c in mix["requests"]["prompt_lengths"]})
    for reqs in (a, b):
        assert Counter(len(p) for _, p, _ in reqs) == want
        assert all(1 <= t < 151935 for _, p, _ in reqs for t in p)
        assert [rid for rid, _, _ in reqs] == list(range(len(reqs)))
    if mix["requests"].get("order") == "interleaved":  # one schedule for every seed
        assert [len(p) for _, p, _ in a] == [len(p) for _, p, _ in b] == (
            traffic_gen.interleaved(mix))
    eng = mix["engine"]
    longest = max(want) + mix["requests"]["new_tokens"]
    assert -(-longest // eng["page_size"]) <= eng["max_pages_per_req"]


def test_pool_sizes_of_the_docqa_mixes():
    pressure, resident = _mix("docqa-pressure"), _mix("docqa-resident")
    assert pressure["requests"] == resident["requests"]
    page, new = pressure["engine"]["page_size"], pressure["requests"]["new_tokens"]
    ws = sum(-(-(n + new) // page) for n in traffic_gen.prompt_lengths(pressure))
    assert ws == 1152
    assert pressure["engine"]["n_hbm_pages"] * 2 == ws
    assert resident["engine"]["n_hbm_pages"] == ws
    assert {k: v for k, v in pressure["engine"].items() if k != "n_hbm_pages"} == {
        k: v for k, v in resident["engine"].items() if k != "n_hbm_pages"}


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_config_keeps_the_source_widths_and_lists_its_cuts(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert not WIDTHS & set(entry["reduced"])
    src = cfg["source_keys"]
    changed = set()
    for key, skey in SOURCE_KEY.items():
        if skey in src and src[skey] != cfg[key]:
            changed.add(key)
    assert changed == set(entry["reduced"])
    for key in entry["reduced"]:
        assert cfg["published"][key] == src[SOURCE_KEY[key]]
    if "head_dim" not in src:
        assert cfg["head_dim"] * cfg["n_heads"] == cfg["d_model"]
    assert cfg["dtype"] == src["torch_dtype"] == "bfloat16"
    assert (ROOT / "bench" / "reference" / f"{cfg['reference']}.py").exists()


def test_flops_match_hand_counts():
    cfg = json.loads((DATA / "tiny.json").read_text())
    # d 256, H 4, KV 2, hd 128, F 512, V 1024, 2 layers
    layer = 256 * 512 + 2 * 256 * 256 + 512 * 256 + 3 * 256 * 512
    assert flops.matmul_params(cfg) == 2 * layer + 256 * 1024
    assert flops.decode_token_flops(cfg, 100) == (
        2 * (2 * layer + 256 * 1024) + 2 * 4 * 4 * 128 * 100)
    # a row at context 100 with 64 log slots over batch 8: watermark >= 92
    assert flops.paged_pages_lower_bound(100, 16, 64, 8) == 6
    assert flops.paged_pages_lower_bound(5, 16, 64, 8) == 0
    # two rows of 6 and 3 pages: K and V pages plus q and out, per layer
    kv = 9 * 16 * 2 * 128 * 2 * 2
    qo = 2 * 4 * 128 * 2 * 2
    assert flops.paged_attn_bytes(cfg, [6, 3], 16) == 2 * (kv + qo)


def test_benchmark_json_keeps_the_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith("bench/") and SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    layers = {m["layer"] for m in SPEC["per_layer"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers
        # read only in cells that report the end-to-end metric it moves
        assert set(m.get("workloads", cells)) <= set(e2e[m["moves"]].get("workloads", cells))
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert m["unit"] == "%" or not m["name"].endswith(("_roofline", "_share"))
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert len(w["why"]) <= 200
        lim = json.loads((ROOT / "bench" / "limits" / f"{w['name']}.json").read_text())
        g = lim["max_logit_gap"]
        assert set(g) >= {"limit", "lower", "upper", "readings"}
        if g["limit"] is not None:  # a limit lies between its two readings
            assert g["lower"] < g["limit"] < g["upper"]


def _parent_table(cfg):
    """The weights' leaf table as `bench/weights.py` drew it before it took
    the configuration's dict (from the program's `ModelConfig`), kept here
    to show that the dict-driven table draws the same bits."""
    import math

    d, L, hd = cfg.d_model, cfg.n_layers, cfg.resolved_head_dim
    H, KV, F, V = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab
    blocks = {
        "attn_norm": ((L, d), "gain"),
        "wq": ((L, d, H * hd), 1 / math.sqrt(d)),
        "wk": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "wv": ((L, d, KV * hd), 1 / math.sqrt(d)),
        "wo": ((L, H * hd, d), 1 / math.sqrt(H * hd)),
        "mlp_norm": ((L, d), "gain"),
        "w_gate": ((L, d, F), 1 / math.sqrt(d)),
        "w_up": ((L, d, F), 1 / math.sqrt(d)),
        "w_down": ((L, F, d), 1 / math.sqrt(F)),
    }
    if cfg.qkv_bias:
        blocks.update(bq=((L, H * hd), 0.02), bk=((L, KV * hd), 0.02),
                      bv=((L, KV * hd), 0.02))
    if cfg.qk_norm:
        blocks.update(q_norm=((L, hd), "gain"), k_norm=((L, hd), "gain"))
    tree = {"embed": ((V, d), 0.02), "final_norm": ((d,), "gain"),
            "blocks": blocks}
    if not cfg.tie_embeddings:
        tree["lm_head"] = ((d, V), 0.02)
    return tree


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_weights_draw_the_parents_bits(entry):
    """`weights.make` on a configuration file's dict, at a small depth and
    width, gives leaves bit-equal to the table drawn from `ModelConfig`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import harness, weights

    cfg = dict(json.loads((ROOT / entry["file"]).read_text()),
               n_layers=1, d_model=64, vocab=512)
    seed = 2**31 + 77
    leaves, treedef = jax.tree_util.tree_flatten(
        _parent_table(harness.model_config(cfg)), is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(key):  # the parent's `make`, one jitted call
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [weights._draw(k, s, sc, jnp.bfloat16) for k, (s, sc) in zip(keys, leaves)])

    parent = build(weights.key_for(seed, 0))
    made = weights.make(cfg, seed)
    assert jax.tree_util.tree_structure(made) == jax.tree_util.tree_structure(parent)
    for a, b in zip(jax.tree_util.tree_leaves(made), jax.tree_util.tree_leaves(parent)):
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
        assert np.array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))


ENGINES = [_mix(m)["engine"] for m in MIXES] + [
    json.loads((DATA / "tiny-mix.json").read_text())["engine"]]


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
@pytest.mark.parametrize("engine", ENGINES, ids=[f"batch{e['batch']}-log{e['log_slots']}"
                                                 for e in ENGINES])
def test_work_counts_equal_the_dense_formulas(entry, engine):
    """The dispatching counts give, for a dense configuration, what the
    readers computed inline before (decode_mfu: 2 per matmul weight plus
    q.k and p.v; paged_attn_roofline: pages from below, then bytes and
    FLOPs per step)."""
    cfg = json.loads((ROOT / entry["file"]).read_text())
    L, H, hd = cfg["n_layers"], cfg["n_heads"], cfg["head_dim"]
    for context in (1, 17, 100, 1025, 2111):
        assert flops.decode_token_flops(cfg, context) == (
            2 * flops.matmul_params(cfg) + L * 2 * 2 * H * hd * context)
    page = engine["page_size"]
    for contexts in ([], [5], [100, 40, 101], [513, 1027, 2049, 530, 1040, 2060, 600, 16],
                     list(range(120, 120 + 32 * 7, 7))):
        pages = [flops.paged_pages_lower_bound(c, page, engine["log_slots"], engine["batch"])
                 for c in contexts]
        want = (flops.paged_attn_bytes(cfg, pages, page),
                L * sum(4 * H * hd * p * page for p in pages))
        assert flops.paged_attn_work(cfg, contexts, engine) == want


def test_model_config_builds_nested_dataclasses():
    from bench import harness
    from repro.configs.base import MoEConfig

    cfg = json.loads((DATA / "tiny-moe.json").read_text())
    mc = harness.model_config(cfg)
    assert mc.family == "moe" and isinstance(mc.moe, MoEConfig)
    assert mc.moe == MoEConfig(num_experts=4, top_k=4, d_ff_expert=256, capacity_factor=1.0)
    assert harness._tuples([1, [2, 3]]) == (1, (2, 3))
    assert harness.model_config(json.loads((DATA / "tiny.json").read_text())).moe is None
