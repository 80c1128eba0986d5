"""The benchmark's pieces for ``trinity-mini.train-sequences-16k-ep8``: the
configuration file against the catalog row's numbers, its parameter count
against the model's own leaves and the counts' own, the roofline counts by
hand and the same whatever an op is called, the seeded state, a tiny-size
CPU rehearsal of the cell through the harness's test-only seam (traced), its
control (one precision lower), the six faults of ISSUE 53 and an unchanged
state, each of which has to be judged not correct, and the parent's program
refusing the cell at once. Nothing here measures a speed."""

import contextlib
import io
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

from benchmark import control, harness, roofline_afmoe  # noqa: E402
from benchmark.drivers import _program_afmoe, train_afmoe  # noqa: E402
from benchmark.readers import roofline_afmoe as reader  # noqa: E402
from benchmark.readers import scope_device_ms as sdm  # noqa: E402

CELL = "trinity-mini.train-sequences-16k-ep8"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONFIG = harness.load_json("configs", "trinity-mini.json")
FLAGS = CONFIG["flags"]
PEAKS = harness.peaks_for("TPU v5 lite")

#: The catalog row ``Trinity-Mini``'s ``config`` (model-configs guide),
#: but its 32 ``layer_types`` (3 windowed to 1 full, below).
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}

# The cell cut to a size a CPU rehearses in seconds (a dense windowed layer,
# the full layer and a windowed layer with experts), in float32 (its control
# is then bfloat16); the limits are this size's own. The window is 1 s (a
# tiny step is milliseconds).
TINY = {
    "config": {"vocabulary_rows": 100,
               "assumed": {"embedding_scale": 3.0 / np.sqrt(32.0)}},
    "flags": {"feature_size": 100, "embedding_size": 32,
              "history_max_len": 32, "decoder_layers": 3,
              "layer_types": "window_attention,full_attention,"
                             "window_attention", "attn_window": 8,
              "dense_mlp_width": 48, "attn_q_heads": 4, "attn_kv_heads": 2,
              "attn_head_dim": 8, "moe_expert_width": 16,
              "moe_shared_width": 16, "moe_pair_capacity": 32 * 8,
              "learning_rate": 1e-3, "compute_dtype": "float32"},
    "traffic": {"shards": 2, "sequences_per_shard": 64,
                "sequence_length": 32,
                "limits": {"xent_gap": 1e-4, "first_moment_gap": 2e-3,
                           "first_moment_gap_unrouted": 2e-3,
                           "param_change_gap": 0.1}},
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NEW = {"train_attn_scores_window_device_ms",
       "train_attn_scores_roofline.afmoe",
       "train_attn_scores_roofline.afmoe_window", "train_step_roofline.afmoe"}
CHECKS = list(train_afmoe.CHECKS)


def rehearse(seed=2 ** 31 + 7, trace=False, **flags):
    over = {**TINY, "flags": {**TINY["flags"], **flags}}
    return harness.run(CELL, seed, 1.0, trace, overrides=over,
                       require_chip=False)


def checks_of(printed):
    """{check: (value, said ok)} of a run's ``check`` lines."""
    return {ln.split()[1].rstrip(":"): (float(ln.split()[2]),
                                        ln.endswith(" ok"))
            for ln in printed.splitlines()
            if ln.startswith("check ") and " (limit " in ln}


# ----------------------------------------------------------- configuration

def test_configuration_runs_every_published_width():
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts_held", "vocabulary_rows"]
    assert entry["source"].startswith(CONFIG["source"]) and CONFIG[
        "source"] == ("https://huggingface.co/arcee-ai/Trinity-Mini/blob/"
                      "main/config.json")
    assert "model_type afmoe" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == "benchmark/configs/trinity-mini.json"
    for key, value in CATALOG.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    types_ = CONFIG["layer_types"]
    assert len(types_) == 32 and all(
        t == ("full_attention" if i % 4 == 3 else "sliding_attention")
        for i, t in enumerate(types_))
    # the cut runs published layers 1-5 under the program's words
    assert FLAGS["layer_types"].split(",") == [
        t.replace("sliding", "window") for t in types_[1:6]]
    published = {"num_hidden_layers": 32, "num_experts_held": 128,
                 "vocabulary_rows": 200192}
    held = {"num_hidden_layers": 5, "num_experts_held": 16,
            "vocabulary_rows": 25024}
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == published[key], key
        assert CONFIG[key] == held[key], key
    # the guide's floors: a leading dense layer and a whole period after
    # it, at least 8 routed experts a layer, an eighth of the vocabulary
    assert held["num_hidden_layers"] - FLAGS["dense_layers"] >= 4
    assert held["num_experts_held"] >= 8
    assert held["vocabulary_rows"] * 8 == published["vocabulary_rows"]
    # every width as published
    assert (FLAGS["embedding_size"], FLAGS["dense_mlp_width"],
            FLAGS["moe_expert_width"], FLAGS["moe_shared_width"],
            FLAGS["attn_q_heads"], FLAGS["attn_kv_heads"],
            FLAGS["attn_head_dim"], FLAGS["attn_window"]) == (
        CATALOG["hidden_size"], CATALOG["intermediate_size"],
        CATALOG["moe_intermediate_size"], CATALOG["moe_intermediate_size"],
        CATALOG["num_attention_heads"], CATALOG["num_key_value_heads"],
        CATALOG["head_dim"], CATALOG["sliding_window"])
    assert (FLAGS["moe_experts"], FLAGS["moe_top_k"],
            FLAGS["moe_route_scale"], FLAGS["rope_theta"],
            FLAGS["rms_norm_eps"]) == (
        CATALOG["num_experts"], CATALOG["num_experts_per_tok"],
        CATALOG["route_scale"], CATALOG["rope_theta"],
        CATALOG["rms_norm_eps"])
    assert (FLAGS["decoder_layers"], FLAGS["dense_layers"],
            FLAGS["moe_experts_held"], FLAGS["moe_first_expert"]) == (
                5, 1, 16, 0)
    assert FLAGS["feature_size"] == CONFIG["vocabulary_rows"] == 25024
    # twice the mean held pairs T * 8 * 16 / 128 at T = 16,384
    assert FLAGS["moe_pair_capacity"] == 2 * (16384 * 8 * 16 // 128)
    assert CONFIG["assumed"]["embedding_scale"] == pytest.approx(
        3.0 / np.sqrt(2048.0))
    for said in ("sandwich_norms", "gate", "qk_norm", "positions", "window",
                 "embedding", "renormalisation_eps", "selection_bias",
                 "balance_loss", "optimizer", "precision", "packing",
                 "weights", "router_placement", "moe_pair_capacity",
                 "from_memory"):
        assert said in CONFIG["assumed"], said
    for said in ("8 chips share each expert layer", "16 of 128",
                 "25,024 of 200,192", "What the cut distorts",
                 "Why not other cuts", "17.7 GB", "an eighth"):
        assert said in CONFIG["deployment"], said


def test_parameter_count_is_the_models_own_leaves():
    import jax

    from benchmark.drivers import _program
    from deepfm_tpu.models import get_model

    model = get_model(_program.make_config(FLAGS))
    shapes, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    table_rows = shapes["tok_emb"].shape[0]
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # (the program's table may hold padding rows past the vocabulary's)
    assert total - (table_rows - 25024) * 2048 == CONFIG["parameters"] \
        == roofline_afmoe.param_count(FLAGS)["all"] == 705473792
    # 4-way experts (32 a layer) would not fit: 17.7 GB at 16 bytes
    wide = roofline_afmoe.param_count({**FLAGS, "moe_experts_held": 32})
    assert wide["all"] == 1108126976 and round(16 * wide["all"] / 1e9, 1) \
        == 17.7


def test_the_cell_its_traffic_and_its_who_are_the_issues():
    cell = harness.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "trinity-mini", "train-sequences-16k-ep8", 1)
    assert len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["kind"], t["shards"], t["sequences_per_shard"],
            t["sequence_length"], t["sequences_per_step"], t["max_epochs"],
            t["zipf_exponent"]) == ("train-sequences", 16, 64, 16384, 1,
                                    1000, 1.05)
    # the other sequence cells' 16.8 M tokens
    assert t["shards"] * t["sequences_per_shard"] * t["sequence_length"] \
        == 16 * 128 * 8192
    assert "window/global" in t["who"] and "16 of 128 experts" in t["who"]
    assert set(t["limits"]) == set(t["limits_why"]) == set(CHECKS)
    assert cell.driver == "train_afmoe"
    assert set(cell.end_to_end) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert {"train_attn_device_ms", "train_attn_scores_device_ms",
            "train_moe_device_ms", "train_mlp_device_ms",
            "train_moe_roofline", "train_step_mfu",
            "train_head_device_ms", "moe_expert_load_max_over_mean",
            "moe_pairs_over_buffer", "device_idle_share.train",
            "peak_hbm_gb.train", "train_step_device_ms",
            "train_embed_device_ms", "train_update_device_ms",
            "train_unscoped_device_ms", "compiles_in_window.train",
            "dispatch_interval_ms_p50", "input_ns_per_record",
            "stage_transfer_ms", "input_wait_ms_max", "input_busy_share",
            "host_beat_late_ms_max", "device_idle_under_host_stall_share",
            } | NEW <= set(cell.per_layer)  # what a later PR adds is welcome
    # entries are found by name, never by position: a later PR appends
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NEW}
    assert set(mine) == NEW
    assert all(m["workloads"] == [CELL] and m["moves"]
               == "train_examples_per_s_per_chip" for m in mine.values())
    assert all(m["unit"] == "%" for n, m in mine.items() if "roofline" in n)


@pytest.mark.parametrize("name", sorted(
    m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]))
def test_each_listed_metric_resolves(name):
    spec = harness.load_json("metrics", f"{name}.json")
    assert os.path.exists(os.path.join(
        harness.BENCH_DIR, "readers", spec["reader"] + ".py")), name


def test_the_scope_metrics_read_each_masks_own_scope():
    assert harness.load_json(
        "metrics", "train_attn_scores_window_device_ms.json") == {
            "reader": "scope_device_ms",
            "args": {"scopes": ["attn_scores_window"]}}
    assert harness.load_json("metrics", "train_attn_scores_device_ms.json")[
        "args"]["scopes"] == ["attn_scores"]
    from deepfm_tpu.utils import profiling
    assert {"attn_scores", "attn_scores_window"} <= set(profiling.STEP_SCOPES)
    # the innermost scope wins: a mask's kernels are its own scope's, the
    # projections around them the block's
    assert profiling.innermost_scope(
        "jit(step)/transpose(jvp(attn))/attn_scores_window/pallas_call") \
        == "attn_scores_window"
    assert profiling.innermost_scope(
        "jit(step)/attn/attn_scores/pallas_call") == "attn_scores"
    assert profiling.innermost_scope("jit(step)/attn/dot_general") == "attn"


# ------------------------------------------------------------- the counts

def test_roofline_counts_by_hand():
    b, t, d, v = 1, 16384, 2048, 25024
    pairs = 4 * 16384.0
    assert roofline_afmoe.allowed_pairs(t) == 134_225_920
    assert roofline_afmoe.allowed_pairs(t, 2048) == 31_458_304
    assert roofline_afmoe.allowed_pairs(24, 5) == sum(
        min(i + 1, 5) for i in range(24))
    assert roofline_afmoe.allowed_pairs(24, 24) \
        == roofline_afmoe.allowed_pairs(24, 99) \
        == roofline_afmoe.allowed_pairs(24)
    flops = roofline_afmoe.forward_flops(FLAGS, pairs)
    # q, the gate and the output over 32 heads, k and v over 4, of 128
    assert flops["attn_projections"] == 2.0 * b * t * 5 * d * 128 * (
        3 * 32 + 2 * 4)
    # allowed pairs x (128 + 128) x 32 heads: one full layer, four windowed
    assert flops["attn_scores"] == 2.0 * 32 * 256 * 134_225_920
    assert flops["attn_scores_window"] == 2.0 * 32 * 256 * 4 * 31_458_304
    assert flops["router"] == 2.0 * b * t * 4 * d * 128
    assert flops["shared"] == 2.0 * b * t * 4 * 3 * d * 1024
    assert flops["experts"] == 2.0 * pairs * 3 * d * 1024
    assert flops["dense_mlp"] == 2.0 * b * t * 3 * d * 6144
    assert flops["head"] == 2.0 * b * (t - 1) * d * v
    # ISSUE 53's arithmetic: 813 M a token forward, the two masks' scores
    # 32% of it, the least step 203 ms
    a_token = sum(flops.values()) / t
    assert 810e6 < a_token < 816e6
    assert 0.31 < (flops["attn_scores"] + flops["attn_scores_window"]) \
        / sum(flops.values()) < 0.33
    step = roofline_afmoe.train_step_least_seconds(FLAGS, pairs, PEAKS)
    assert step["bound"] == "flops"
    assert step["flops"] == 3.0 * sum(flops.values())
    assert step["bytes"] == 40.0 * 705473792
    assert 0.20 < step["seconds"] < 0.205
    for share, layers in (("attn_scores", 1), ("attn_scores_window", 4)):
        scores = roofline_afmoe.attn_scores_least_seconds(FLAGS, PEAKS,
                                                          share)
        assert scores["flops"] == 3.0 * flops[share]
        assert scores["bytes"] == 2.0 * 2 * 2 * 36 * 128 * b * t * layers
        assert scores["bound"] == "flops"


#: A mask's score kernels as a compiled step could name them: JAX's own, a
#: plain fusion, anything else; the same results and the same own time each.
KERNEL_NAMES = ("splash_mqa_fwd_residuals.3", "fusion.12", "scores.7")
OWN_S = {"scores": 0.9, "window": 1.8, "attn": 5.0}


def _ctx(monkeypatch, kernel=KERNEL_NAMES[0], trace=True, pairs=65536.0,
         scopes=("attn_scores", "attn_scores_window", "attn")):
    """A context whose newest trace holds three ops, one under each of
    ``scopes``, the first named ``kernel``."""
    text = "\n".join([
        "ENTRY %main.9 () -> f32[] {",
        f"  %{kernel} = bf16[32,16384,128]{{2,1,0:T(8,128)(2,1)}} "
        "custom-call(%a, %b), custom_call_target=\"x\"",
        "  %fusion.7 = bf16[4,32,16384,128]{3,2,1,0} fusion(%h), kind=kLoop",
        "  %fusion.9 = f32[16384,4096]{1,0:T(8,128)} fusion(%q), kind=kOutput",
        "}"])
    names = (kernel, "fusion.7", "fusion.9")
    op_scopes = sdm.keyed_scopes(text, dict(zip(names, scopes)))
    ops = {sdm.op_key(line): OWN_S[what] for line, what in zip(
        text.splitlines()[1:4], ("scores", "window", "attn"))}
    monkeypatch.setattr(sdm, "_reduced", {})
    monkeypatch.setattr(sdm, "newest_trace", lambda name: "a.xplane.pb")
    monkeypatch.setattr(sdm, "own_seconds", lambda path, w: (dict(ops), 0.0))
    monkeypatch.setattr(sdm, "program_op_scopes", lambda ctx: op_scopes)
    counters = {"steps_in_window": 10}
    if pairs:
        counters["moe_pairs_held_per_step"] = pairs
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(name=CELL, config={"flags": FLAGS}),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        counters=counters, window=(0, 12 * 10 ** 9),
        trace={"devices": 1, "busy_s": 11.0, "window_s": 12.0}
        if trace else None)


@pytest.mark.parametrize("share, own", [("attn_scores", "scores"),
                                        ("attn_scores_window", "window")])
def test_a_masks_share_is_its_pairs_over_its_scopes_time(monkeypatch, share,
                                                         own):
    """... and the same for ``splash_mqa_*``, a ``fusion`` and any other op
    name: the reader finds the work by the scope, never by a name."""
    least = roofline_afmoe.attn_scores_least_seconds(FLAGS, PEAKS, share)
    want = 100 * least["seconds"] / (OWN_S[own] / 10)
    got = [reader.read(_ctx(monkeypatch, kernel), share)
           for kernel in KERNEL_NAMES]
    assert got[0] == pytest.approx(want, rel=1e-12) and 0 < got[0] < 100
    assert got[0] == got[1] == got[2]
    # and the scope metric beside it reads the time it divided by
    assert sdm.read(_ctx(monkeypatch), [share]) == pytest.approx(
        1e3 * OWN_S[own] / 10)
    with open(os.path.join(harness.BENCH_DIR, "readers",
                           "roofline_afmoe.py")) as f:
        assert "splash" not in f.read()


def test_the_steps_share_and_nothing_to_read(monkeypatch):
    least = roofline_afmoe.train_step_least_seconds(FLAGS, 65536.0, PEAKS)
    assert reader.read(_ctx(monkeypatch), "step") == pytest.approx(
        100 * least["seconds"] / 1.1)
    for share in ("step", "attn_scores", "attn_scores_window"):
        assert reader.read(_ctx(monkeypatch, trace=False), share) is None
        assert reader.read(_ctx(monkeypatch, pairs=0), share) is None
    # a program from before the scope (a map that knows the ops and has
    # no such scope in it): nothing to read, and nothing raised
    assert reader.read(_ctx(monkeypatch, scopes=("attn", "attn", "attn")),
                       "attn_scores_window") is None
    with pytest.raises(ValueError):
        reader.read(_ctx(monkeypatch), "moe_matmul")
    # the whole step's share of the peak finds this model's count by name
    from benchmark.readers import train_step_mfu
    assert train_step_mfu.read(_ctx(monkeypatch)) == pytest.approx(
        100 * least["flops"] * 10 / (197e12 * 12.0))


# -------------------------------------------------------- the seeded state

def test_the_seeded_leaves_follow_their_rules():
    import jax.numpy as jnp

    from benchmark import weights
    cfg = types.SimpleNamespace(
        decoder_layers=2, dense_layers=1, moe_top_k=8, moe_experts=128,
        moe_experts_held=16, moe_first_expert=0, feature_size=100)
    trainer = types.SimpleNamespace(
        cfg=cfg, model=types.SimpleNamespace(padded_vocab=128))
    scale = 3.0 / np.sqrt(32.0)
    kw = _program_afmoe.weight_kwargs(
        {"assumed": {"embedding_scale": scale}}, trainer)
    plan = kw["router_plan"]
    # P = 1: every heavy class has one held expert of its 8 in the layer
    assert plan["boost"].shape == (2, 8, 128)
    assert np.all((plan["boost"][1] > 0).sum(axis=-1) == 8)
    assert np.all((plan["boost"][1][:, :16] > 0).sum(axis=-1) == 1)
    assert _program_afmoe.bias_shape(cfg) == (1, 128)
    names = {"layers.0.norm1_post": (32,), "layers.1.norm2_post": (32,),
             "layers.0.q_norm": (8,), "layers.0.k_norm": (8,),
             "layers.1.norm1": (32,), "final_norm": (32,),
             "layers.0.wg": (32, 32), "layers.1.router": (32, 128),
             "tok_emb": (128, 32)}
    salts = {n: weights.leaf_salt(7, n) for n in names}
    for xp in (np, jnp):
        got = {n: np.asarray(_program_afmoe.seeded_leaf(
            salts, n, shape, kw, xp=xp)) for n, shape in names.items()}
        for gain in ("layers.0.norm1_post", "layers.1.norm2_post",
                     "layers.0.q_norm", "layers.0.k_norm", "layers.1.norm1",
                     "final_norm"):
            # the sublayers' output norms at a quarter: the stream stays
            # its token's
            unit = got[gain] / (_program_afmoe.POST_GAIN
                                if gain.endswith("_post") else 1.0)
            assert np.all(np.abs(unit - 1.0) <= 0.1 + 1e-6), gain
            assert np.std(unit) > 0.01, gain
        assert _program_afmoe.POST_GAIN == 0.25
        assert not np.array_equal(got["layers.0.q_norm"],
                                  got["layers.0.k_norm"])
        assert np.abs(got["layers.0.wg"]).max() <= np.sqrt(6 / 64)
        # the table at 3 / sqrt(d): the stream's first state in +-3
        assert 2.5 < np.abs(got["tok_emb"][:100]).max() * np.sqrt(32) <= 3.0
        assert not got["tok_emb"][100:].any()
        plain = np.asarray(weights.leaf_values(
            salts["layers.1.router"], (32, 128), feature_size=100,
            padded_vocab=128, embedding_scale=scale))
        assert np.abs(got["layers.1.router"] - plain).max() > 0.01
    bias = _program_afmoe.seeded_bias(
        weights.leaf_salt(7, _program_afmoe.SELECT_BIAS), (4, 128))
    assert bias.shape == (4, 128) and 0.01 < np.abs(bias).max() <= 0.02


# ------------------------------------------------------------- rehearsals

@pytest.fixture(scope="module")
def traced():
    """(the line, what was printed) of the one sound rehearsal, traced."""
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        out = rehearse(trace=True)
    return out, printed.getvalue()


def test_rehearsal_prints_the_contracts_keys(traced):
    line, _ = traced
    assert set(line) == LINE_KEYS | {"breakdown"} and line["correct"] is True
    assert line["metrics"] and all(
        set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_rehearsal_prints_what_a_cpu_can_read(traced):
    """A traced run's line carries each per-layer metric the cell lists
    that has something to read on a CPU (no device plane in its trace: the
    device-trace metrics, the four new ones among them, are left out, not
    failed); the counts' line says the bias's picks; the check names its
    seven numbers."""
    out, printed = traced
    cell = harness.load_cell(CELL)
    assert set(out["metrics"]) <= set(cell.per_layer)
    assert {"compiles_in_window.train", "dispatch_interval_ms_p50",
            "input_ns_per_record", "moe_expert_load_max_over_mean",
            "moe_pairs_over_buffer"} <= set(out["metrics"])
    assert not NEW & set(out["metrics"])
    counts = [ln for ln in printed.splitlines()
              if ln.startswith("counts (")][-1]
    assert float(counts.split("moe_bias_moved_picks ")[1].split(";")[0]) > 0
    checks = checks_of(printed)
    assert list(checks) == CHECKS and all(ok for _, ok in checks.values())
    leaves = [ln.split()[1].rstrip(":") for ln in printed.splitlines()
              if ln.startswith("leaf ")]
    assert {"layers.0.norm1_post", "layers.1.norm2_post", "layers.1.wg",
            "layers.1.q_norm", "layers.2.router", "head",
            "tok_emb"} <= set(leaves)


def test_one_precision_lower_is_judged_not_correct():
    out = control.run(CELL, 5, 1.0, overrides=TINY, require_chip=False)
    assert out["correct"] is False


#: fault -> a check that has to refuse it (others may too)
REFUSED_BY = {
    "full_layer_rotated": "first_moment_gap_unrouted",
    "gate_dropped": "first_moment_gap_unrouted",
    "post_norm_skipped": "first_moment_gap_unrouted",
    "embed_scale_dropped": "first_moment_gap_unrouted",
    "route_scale_one": "first_moment_gap",
    "windowed_run_causal": "first_moment_gap_unrouted",
}


@pytest.mark.parametrize("fault", sorted(REFUSED_BY))
def test_each_of_the_six_faults_is_caught(monkeypatch, capsys, fault):
    """ISSUE 53's faults at the rehearsal's size (``tests/test_afmoe`` has
    the patches): the full layer rotated, the gate dropped, a post-norm
    skipped, the embedding's constant dropped, ``route_scale`` 1, the
    windowed layers run causal."""
    import test_afmoe

    test_afmoe.FAULTS[fault](monkeypatch)
    assert rehearse()["correct"] is False
    value, ok = checks_of(capsys.readouterr().out)[REFUSED_BY[fault]]
    assert not ok


def test_a_step_that_leaves_the_parameters_unchanged_is_caught(
        monkeypatch, capsys):
    """A state left as it was reads a ``param_change_gap`` of 1, over the
    limit (the cell's 0.6 as the rehearsal's 0.1)."""
    import deepfm_tpu.train.loop as loop

    monkeypatch.setattr(loop.optax, "apply_updates",
                        lambda params, updates: params)
    line = rehearse()
    assert line["correct"] is False
    # an untraced line: the contract's keys and the two end-to-end metrics
    assert set(line) == LINE_KEYS and set(line["metrics"]) == {
        "train_examples_per_s_per_chip", "setup_s"}
    value, ok = checks_of(capsys.readouterr().out)["param_change_gap"]
    assert not ok and value == pytest.approx(1.0, abs=1e-3)


def test_the_parent_program_fails_the_cell_at_once(monkeypatch):
    """A program that does not know the model (the parent of PR 53) is
    refused where the configuration is built, before a shard is written or
    a device touched."""
    from benchmark.drivers import _program

    def unknown(flags):
        raise ValueError("unknown model: 'afmoe'")
    monkeypatch.setattr(_program, "make_config", unknown)
    started = []
    monkeypatch.setattr(train_afmoe.traffic_sequences, "ShardWriter",
                        lambda *a, **k: started.append(a))
    with pytest.raises(ValueError, match="unknown model"):
        harness.run(CELL, 1, 1.0, False, overrides=TINY, require_chip=False)
    assert not started
