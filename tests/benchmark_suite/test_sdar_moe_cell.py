"""The benchmark's pieces for ``sdar-30b-a3b.train-sequences``: the
configuration file against the catalog row's numbers, the parameter and byte
count of the cut by hand, the roofline count by hand, the traffic writer
against the repo's own codec, the readers on hand-made contexts, a tiny-size
CPU rehearsal of the cell through the harness's test-only seam, its control
(one precision lower), two broken programs that have to be judged not
correct, and the parent's program refusing the cell at once. Nothing here
measures a speed. (``test_benchmark.py`` binds the new entries of
``BENCHMARK.json`` to their files and to the contract.)"""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (control, harness, reference_sdar_moe,  # noqa: E402
                       roofline_sdar_moe, traffic_sequences)
from benchmark.drivers import train_sdar_moe  # noqa: E402
from benchmark.readers import roofline_moe  # noqa: E402
from benchmark.readers import roofline_sdar_moe as reader  # noqa: E402

CELL = "sdar-30b-a3b.train-sequences"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONFIG = harness.load_json("configs", "sdar-30b-a3b.json")
FLAGS = CONFIG["flags"]

#: The catalog row ``SDAR-30B-A3B-Chat``'s ``config`` (model-configs guide).
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}

# The cell cut to a size a CPU rehearses in seconds, in float32 (its control
# is then bfloat16): at widths of 8 to 32 a bfloat16 step is far noisier than
# at 128 to 2048, and the limits are this size's own.
TINY = {
    "config": {"vocabulary_rows": 100},
    "flags": {"feature_size": 100, "embedding_size": 32,
              "history_max_len": 16, "decoder_layers": 2, "attn_q_heads": 2,
              "attn_kv_heads": 1, "attn_head_dim": 8, "moe_experts": 8,
              "moe_top_k": 2, "moe_expert_width": 16, "moe_experts_held": 4,
              "moe_pair_capacity": 128, "learning_rate": 1e-3,
              "compute_dtype": "float32"},
    "traffic": {"shards": 2, "sequences_per_shard": 64,
                "sequence_length": 16,
                "limits": {"xent_gap": 1e-4, "first_moment_gap": 2e-3,
                           "param_change_gap": 0.1,
                           "untouched_rows_moved": 0, "noise_z": 5.0,
                           "masked_count_gap": 0,
                           "pairs_over_buffer": 0}},
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(seed=2 ** 31 + 7, **flags):
    over = {**TINY, "flags": {**TINY["flags"], **flags}}
    return harness.run(CELL, seed, 1.0, False, overrides=over,
                       require_chip=False)


# ----------------------------------------------------------- configuration

def test_configuration_runs_every_published_width():
    entry = next(c for c in BENCH["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts_held", "attention_heads_held",
        "key_value_heads_held", "vocabulary_rows"]
    assert entry["source"] == CONFIG["source"] and "sdar_moe" in entry["source"]
    for key, value in CATALOG.items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    published = {"num_hidden_layers": 48, "num_experts_held": 128,
                 "attention_heads_held": 32, "key_value_heads_held": 4,
                 "vocabulary_rows": 151936}
    held = {"num_hidden_layers": 6, "num_experts_held": 16,
            "attention_heads_held": 4, "key_value_heads_held": 1,
            "vocabulary_rows": 18992}
    for key in CONFIG["reduced"]:
        assert CONFIG["published"][key] == published[key], key
        assert CONFIG[key] == held[key], key
    # an eighth of each of a layer's parts, the guide's floors kept
    assert all(held[k] * 8 == published[k] for k in (
        "num_experts_held", "attention_heads_held", "vocabulary_rows"))
    # a key/value head lives on 2 of the 8 chips
    assert held["key_value_heads_held"] * 8 == 2 * 4
    assert held["num_hidden_layers"] >= 4 and held["num_experts_held"] >= 8
    # the flags the program is built with say the same, every width whole
    assert (FLAGS["embedding_size"], FLAGS["attn_head_dim"],
            FLAGS["moe_expert_width"], FLAGS["moe_experts"],
            FLAGS["moe_top_k"]) == (2048, 128, 768, 128, 8)
    assert (FLAGS["decoder_layers"], FLAGS["moe_experts_held"],
            FLAGS["attn_q_heads"], FLAGS["attn_kv_heads"],
            FLAGS["feature_size"]) == (6, 16, 4, 1, 18992)
    assert FLAGS["rope_theta"] == CATALOG["rope_theta"]
    assert FLAGS["rms_norm_eps"] == CATALOG["rms_norm_eps"]
    assert CONFIG["assumed"]["mask_token_row"] == FLAGS["feature_size"] - 1
    assert CONFIG["assumed"]["block_length"] == FLAGS["diffusion_block"] == 4
    assert CONFIG["assumed"]["t_min"] == FLAGS["diffusion_t_min"] == 0.001
    assert (FLAGS["optimizer"], FLAGS["learning_rate"], FLAGS["l2_reg"],
            FLAGS["compute_dtype"]) == ("Adam", 1e-05, 0.0, "bfloat16")
    for key in ("qk_norm", "noise_schedule", "balance_loss", "packing",
                "weights", "precision"):
        assert CONFIG["assumed"][key]
    assert "8 chips share each layer" in CONFIG["deployment"]


def test_the_cell_its_traffic_and_its_who_are_the_issues():
    cell = harness.load_cell(CELL)
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "sdar-30b-a3b", "train-sequences", 1)
    assert len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["shards"], t["sequences_per_shard"], t["sequence_length"],
            t["sequences_per_step"], t["block_length"], t["t_min"],
            t["zipf_exponent"]) == (16, 256, 4096, 2, 4, 0.001, 1.05)
    assert t["who"] == (
        "a team continuing the training of an autoregressive MoE checkpoint "
        "as a block-diffusion model, SDAR's own recipe, on packed 4k-token "
        "sequences with each expert-parallel rank holding 16 of 128 experts")
    assert cell.driver == "train_sdar_moe"
    assert set(cell.end_to_end) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert {"train_attn_device_ms", "train_moe_device_ms",
            "train_head_device_ms", "train_moe_roofline",
            "train_step_roofline.sdar_moe", "moe_expert_load_max_over_mean",
            "moe_pairs_over_buffer", "device_idle_share.train",
            "peak_hbm_gb.train", "train_step_device_ms",
            "train_embed_device_ms", "train_update_device_ms",
            "train_dense_device_ms",      # none of its scopes here: reads 0
            "train_unscoped_device_ms", "compiles_in_window.train",
            "dispatch_interval_ms_p50", "input_ns_per_record",
            "stage_transfer_ms", "input_wait_ms_max", "input_busy_share",
            } <= set(cell.per_layer)        # what a later PR adds is welcome
    # A listed metric has to be in every traced line, and `host_gc_ms_max`
    # is left out of a window in which no collection ran: 20 dispatches
    # here, and none ran in any chip run. The cell is not on its list.
    gc = next(m for m in BENCH["per_layer"] if m["name"] == "host_gc_ms_max")
    assert CELL not in gc["workloads"]
    for name in cell.per_layer:
        spec = harness.load_json("metrics", f"{name}.json")
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "readers", spec["reader"] + ".py")), name


def test_parameter_and_byte_count_of_the_cut_by_hand():
    layer = (2048 * 512            # wq: 4 query heads of 128
             + 2 * 2048 * 128      # wk, wv: 1 key/value head
             + 512 * 2048          # wo
             + 2048 + 2048 + 128 + 128    # the four norms
             + 2048 * 128          # router, all 128 outputs
             + 16 * 3 * 2048 * 768)       # 16 experts, three matrices each
    assert layer == 78_385_408
    ends = 2 * 18992 * 2048 + 2048        # embedding, head, final norm
    got = roofline_sdar_moe.param_count(FLAGS)
    assert got == {"layer": layer, "ends": ends, "all": 6 * layer + ends}
    assert got["all"] == 548_105_728
    # float32 weight and gradient, Adam's two moments: 16 bytes a parameter
    assert round(16 * got["all"] / 1e9, 2) == 8.77


def test_roofline_count_by_hand():
    flags = {**FLAGS, "history_max_len": 8, "batch_size": 1,
             "decoder_layers": 1, "feature_size": 10, "embedding_size": 4,
             "attn_q_heads": 2, "attn_kv_heads": 1, "attn_head_dim": 2,
             "moe_experts": 8, "moe_expert_width": 3}
    # L=8, b=4: 2 blocks; allowed entries 4*4*2*3 = 96 of 16*16
    assert roofline_sdar_moe.allowed_scores(8, 4) == 96
    got = roofline_sdar_moe.forward_flops(flags, pairs=5)
    assert got == {
        "projections": 2.0 * 16 * (4 * (4 + 2 * 2) + 4 * 4),
        "attention": 2.0 * 2 * 4 * 96,      # QK and PV, 2 heads of 2
        "router": 2.0 * 16 * 4 * 8,
        "experts": 2.0 * 5 * 3 * 4 * 3,
        "head": 2.0 * 8 * 4 * 10}
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    least = roofline_sdar_moe.train_step_least_seconds(flags, 5, peaks)
    assert least["flops"] == 3 * sum(got.values())
    assert least["bound"] == "flops"
    assert least["seconds"] == least["flops"] / 1e3
    assert roofline_moe.least_seconds(flags, 5, peaks) \
        == 3 * got["experts"] / 1e3
    # the cell's own, by ISSUE 31's arithmetic: a position costs a layer 5.2
    # MFLOP of projections, 4.2 of allowed scores and values, 10.0 of router
    # and experts; the head 77.8 MFLOP a noisy position
    cell = roofline_sdar_moe.forward_flops(FLAGS, pairs=6 * 16384)
    per = 6 * 16384
    assert round(cell["projections"] / per / 1e6, 1) == 5.2
    assert round(cell["attention"] / per / 1e6, 1) == 4.2
    assert round((cell["router"] + cell["experts"]) / per / 1e6, 1) == 10.0
    assert round(cell["head"] / 8192 / 1e6, 1) == 77.8


def test_roofline_reader_shares_and_nothing_to_read(monkeypatch, tmp_path):
    def ctx(trace=True, **counters):
        return types.SimpleNamespace(
            cell=types.SimpleNamespace(name=CELL, config={"flags": FLAGS}),
            devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
            counters={"steps_in_window": 10, **counters},
            trace={"devices": 1, "busy_s": 3.0} if trace else None,
            window=(0, 1))
    pairs = 6 * 16384.0
    least = roofline_sdar_moe.train_step_least_seconds(
        FLAGS, pairs, harness.peaks_for("TPU v5 lite"))["seconds"]
    assert reader.read(ctx(moe_pairs_held_per_step=pairs), "step") \
        == pytest.approx(100 * least / 0.3)
    # the parent's program counts no pairs; an untraced run has no trace
    assert reader.read(ctx(), "step") is None
    assert reader.read(ctx(trace=False, moe_pairs_held_per_step=pairs),
                       "step") is None
    # the expert layer's share: the routed pairs' three products, forward
    # and backward, over the whole of scope `moe` (the products, their
    # metadata, the rows' kernels, the router), whatever the ops are called
    scope_ms = roofline_moe.scope_device_ms
    monkeypatch.setattr(scope_ms, "newest_trace", lambda cell: "a.xplane.pb")
    monkeypatch.setattr(scope_ms, "_reduced", {})
    ops = {"ragged-dot-none.3 bf16[20480,768]": 0.2,
           "ragged-dot-metadata.1 s32[17]": 0.5,
           "ragged-dot-none.9 f32[16,2048,768]": 0.2,
           "moe_take_rows.2 bf16[20480,2048]": 0.3,
           "fusion.7 f32[2,8192,2048]": 9.0}
    monkeypatch.setattr(scope_ms, "own_seconds",
                        lambda path, window: (dict(ops), 0.0))
    monkeypatch.setattr(scope_ms, "program_op_scopes", lambda ctx: {
        **{key: "moe" for key in ops}, "fusion.7 f32[2,8192,2048]": "attn"})
    want = 100 * 3 * roofline_sdar_moe.forward_flops(FLAGS, pairs)["experts"] \
        / 197e12 / (1.2 / 10)
    assert roofline_moe.read(ctx(moe_pairs_held_per_step=pairs)) \
        == pytest.approx(want)
    assert 0 < want < 100
    assert roofline_moe.read(ctx()) is None
    with pytest.raises(ValueError):
        reader.read(ctx(moe_pairs_held_per_step=pairs), "moe_matmul")


# ----------------------------------------------------------------- traffic

def test_shards_are_the_repos_own_records(tmp_path):
    from deepfm_tpu.data import example_codec, tfrecord

    params = {"zipf_exponent": 1.05}
    tokens = traffic_sequences.generate_tokens(5, 64, 1000, 2 ** 31 + 5,
                                               params)
    again = traffic_sequences.generate_tokens(5, 64, 1000, 2 ** 31 + 5,
                                              params)
    other = traffic_sequences.generate_tokens(5, 64, 1000, 2 ** 31 + 5,
                                              params, stream=1)
    assert tokens.dtype == np.int32 and (tokens == again).all()
    assert (tokens != other).any()
    assert 0 <= tokens.min() and tokens.max() <= 998      # never [MASK]
    path = traffic_sequences.write_shard(str(tmp_path / "tr-0.tfrecord"),
                                         tokens)
    want = str(tmp_path / "want.tfrecord")
    with tfrecord.TFRecordWriter(want) as w:
        for row in tokens:
            w.write(example_codec.encode_ctr_example(
                0.0, np.zeros(1), np.ones(1), hist_ids=row))
    assert open(path, "rb").read() == open(want, "rb").read()


def test_gaps_count_untouched_rows_that_moved():
    rng = np.random.default_rng(0)
    params0 = {"tok_emb": rng.normal(size=(8, 4)).astype(np.float32),
               "head": rng.normal(size=(4, 6)).astype(np.float32)}
    f = types.SimpleNamespace(
        params={"tok_emb": params0["tok_emb"][:6] + 1.0,
                "head": params0["head"] + 1.0},
        mu={"tok_emb": np.ones((6, 4), np.float32),
            "head": np.ones((4, 6), np.float32)})
    touched = np.array([1, 1, 0, 1, 0, 0, 0, 0], bool)
    got_p = {"tok_emb": params0["tok_emb"].copy(),
             "head": (params0["head"] + 1.0).reshape(-1)}
    got_p["tok_emb"][touched] += 1.0
    got_m = {"tok_emb": np.zeros((8, 4), np.float32),
             "head": np.ones(24, np.float32)}
    got_m["tok_emb"][touched] = 1.0
    want_m = {"tok_emb": f.mu["tok_emb"], "head": f.mu["head"].reshape(-1)}
    same = train_sdar_moe.step_gaps(got_p, got_m, [0.5, 0.5], f.params,
                                    want_m, [0.5, 0.5], params0, touched)
    assert same["untouched_rows_moved"] == 0 and same["xent_gap"] == 0
    assert same["first_moment_gap"] < 1e-6
    assert same["param_change_gap"] < 1e-6
    got_p["tok_emb"][7, 1] += 1e-6      # a padding row, one element
    got_m["tok_emb"][2] += 1e-12        # an untouched row's moment
    got_p["head"][3] += 0.5
    got = train_sdar_moe.step_gaps(got_p, got_m, [0.5, 0.5, 0.5], f.params,
                                   want_m, [0.5, 0.45, 0.25], params0,
                                   touched)
    assert got["untouched_rows_moved"] == 1 + 4 and got["xent_gap"] == 0.25
    assert got["param_change_leaf"] == "head"
    assert got["param_change_gap"] == pytest.approx(0.5 / np.sqrt(24))


# ------------------------------------------------------ the seeded router

def _router_of(layers, seed=2 ** 31 + 5):
    """(cfg, plan, the seeded router [layers, d, E], the heavy classes' rows
    of the seeded token table) at the cell's widths, in NumPy."""
    from benchmark import weights
    from benchmark.drivers import _program, _program_sdar_moe as seeding

    cfg = _program.make_config({**FLAGS, "decoder_layers": layers})
    plan = seeding.router_plan(cfg)
    kw = {"feature_size": cfg.feature_size, "padded_vocab": 19008,
          "embedding_scale": CONFIG["assumed"]["embedding_scale"]}
    salts = {n: weights.leaf_salt(seed, n)
             for n in (seeding.ROUTER, seeding.TABLE)}
    shape = (layers, cfg.embedding_size, cfg.moe_experts)
    router = seeding.seeded_leaf(salts, seeding.ROUTER, shape,
                                 {**kw, "router_plan": plan})
    rows = weights.leaf_values(salts[seeding.TABLE],
                               (19008, cfg.embedding_size),
                               rows=plan["rows"], **kw)
    return cfg, plan, router, rows, salts, kw


def test_the_seeded_router_places_each_heavy_class_one_expert_in_eight_here():
    from benchmark import traffic_sequences

    cfg, plan, router, rows, _, _ = _router_of(layers=6)
    assert plan["rows"][0] == cfg.feature_size - 1          # [MASK]
    assert list(plan["rows"][1:]) == list(
        traffic_sequences.tokens_of_ranks(np.arange(8), cfg.feature_size))
    placed = plan["boost"] > 0
    assert (placed.sum(-1) == cfg.moe_top_k).all()
    assert (placed[:, :, :cfg.moe_experts_held].sum(-1) == 1).all()
    # a layer's classes sit on different held experts
    assert (placed[:, :, :cfg.moe_experts_held].sum(1) <= 1).all()
    # and the router routes a class's own stream there, by a wide margin
    xn = rows / np.sqrt(np.mean(rows * rows, axis=1, keepdims=True))
    logits = np.einsum("cd,lde->lce", xn, router)
    top = np.argsort(-logits, axis=-1)[..., :cfg.moe_top_k]
    for layer in range(6):
        for c in range(len(plan["rows"])):
            assert set(top[layer, c]) == set(np.nonzero(placed[layer, c])[0])
    margin = np.sort(logits, axis=-1)
    assert (margin[..., -cfg.moe_top_k] - margin[..., -cfg.moe_top_k - 1]
            ).min() > 6.0


def test_the_router_is_seeded_alike_in_numpy_and_on_the_device():
    import jax.numpy as jnp

    from benchmark.drivers import _program_sdar_moe as seeding

    cfg, plan, router, _, salts, kw = _router_of(layers=2)
    on_device = seeding.seeded_leaf(salts, seeding.ROUTER, router.shape,
                                    {**kw, "router_plan": plan}, xp=jnp)
    assert np.array_equal(np.asarray(on_device), router)
    plain = seeding.seeded_leaf({"layers.wq": salts[seeding.ROUTER]},
                                "layers.wq", router.shape,
                                {**kw, "router_plan": plan})
    assert np.abs(router - plain).max() == pytest.approx(
        seeding.ROUTER_BOOST * 3.0 / (3.0 * np.sqrt(2048 / 3.0)), rel=0.05)


# ------------------------------------------------------------- rehearsals

@pytest.fixture(scope="module")
def line():
    return rehearse()


def test_rehearsal_prints_the_contracts_keys(line):
    assert set(line) == LINE_KEYS and line["correct"] is True
    assert set(line["metrics"]) == {"train_examples_per_s_per_chip",
                                    "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_one_precision_lower_is_judged_not_correct():
    out = control.run(CELL, 5, 1.0, overrides=TINY, require_chip=False)
    assert out["correct"] is False


def test_a_step_that_leaves_the_parameters_unchanged_is_caught(monkeypatch):
    import deepfm_tpu.train.loop as loop

    monkeypatch.setattr(loop.optax, "apply_updates",
                        lambda params, updates: params)
    assert rehearse()["correct"] is False


def test_pairs_over_the_buffer_fail_the_run(capsys):
    assert rehearse(moe_pair_capacity=8)["correct"] is False
    out = capsys.readouterr().out
    over = [ln for ln in out.splitlines()
            if ln.startswith("check pairs_over_buffer")][-1]
    assert over.endswith("NOT OK")


def test_the_parent_program_fails_the_cell_at_once(monkeypatch):
    """A program that does not know the model (the parent of PR 31) is
    refused where the configuration is built, before a shard is written or
    a device touched."""
    from benchmark.drivers import _program

    def unknown(flags):
        raise TypeError("Config.__init__() got an unexpected keyword "
                        "argument 'decoder_layers'")
    monkeypatch.setattr(_program, "make_config", unknown)
    started = []
    monkeypatch.setattr(train_sdar_moe.traffic_sequences, "ShardWriter",
                        lambda *a, **k: started.append(a))
    with pytest.raises(TypeError):
        harness.run(CELL, 1, 1.0, False, overrides=TINY, require_chip=False)
    assert not started
