"""What every decoder (``--model sdar_moe``, ``kimi_linear``, ``solar_open2``,
``lfm2_moe``, ``phi4_flash``) is held to at small widths on the CPU, from seeded weights,
against its plain reference under ``benchmark/``: written once, read by each
model's file through a ``Spec``.

A decoder joins by a spec, a subclass and its own tests::

    SPEC = Spec(ref=reference_<model>, small={...}, sizes={...}, ...)

    class Test<Model>(DecoderContract, HybridStack): spec = SPEC

``DecoderContract`` is what all five take (logits and loss, every leaf's
gradient, three Adam steps on one device and on two replicas, bfloat16 told
apart, the compiled step's scopes, what ``Config`` refuses); ``HybridStack``
(each layer kind's forward, the shares, a fit from shards) is for the models
that are ``KimiLinear`` and its subclasses; ``SmallBuffer`` and
``RowKernels`` for those that hold those tests (in a class of their own
where the model's file is split). Where a model's mathematics
differs (SDAR's loss is over masked positions, LFM2 carries a selection
bias) its class overrides the method that says so: ``reference_loss``,
``reference_inputs``, ``start_state``, ``follower``.

Each small program is built once a model: the class-scoped fixtures hold the
seeded state and the float32 one-device trainer with its compiled step, and
the tests read them. A test that needs another program (two replicas,
bfloat16, a small buffer, the row kernels through the interpreter) builds
exactly that one. (Not collected by name: no ``test_`` in the file's.)"""

import collections
import dataclasses
import functools
import os
import re
import sys
from types import SimpleNamespace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.drivers import _program  # noqa: E402
from benchmark.reference_sdar_moe import leaf_gap, worst_leaf_gap  # noqa: E402
from deepfm_tpu.config import Config  # noqa: E402
from deepfm_tpu.data import example_codec, tfrecord  # noqa: E402
from deepfm_tpu.models import get_model, kimi_linear, sdar_moe  # noqa: E402
from deepfm_tpu.ops import pallas_grouped_dot, pallas_moe_rows  # noqa: E402
from deepfm_tpu.parallel import mesh as mesh_lib  # noqa: E402
from deepfm_tpu.train import Trainer  # noqa: E402

F32 = jnp.dtype("float32")
#: Adam steps a trainer is followed for.
STEPS = 3
#: Adam's first moment, float32 program against float32 reference (sums in
#: another order); bfloat16 has to miss it tenfold.
TOL = 2e-4
#: The reference's products, whatever the backend's default.
highest = functools.partial(jax.default_matmul_precision, "highest")


def trainer_on(n_dev, cfg):
    return Trainer(cfg, mesh_info=mesh_lib.build_mesh(
        cfg, devices=jax.devices()[:n_dev]))


def batch_of(tokens):
    n = tokens.shape[0]
    return {"feat_ids": np.zeros((n, 1), np.int32),
            "feat_vals": np.ones((n, 1), np.float32),
            "label": np.zeros((n, 1), np.float32), "hist_ids": tokens,
            "hist_mask": np.ones(tokens.shape, np.float32)}


def off_one(key, tree):
    """``tree`` with every gain (a leaf of ones) moved off one."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape)
        if bool(jnp.all(x == 1.0)) else x for k, x in zip(keys, leaves)])


def products_in_scope(text: str, scope: str):
    """(matrix products, those of them made again in the backward pass) of a
    compiled TPU step's text under the named scope ``scope``: the
    ``convolution`` instructions whose ``op_name`` has the scope, and among
    them ``jax.checkpoint``'s ``rematted_computation``."""
    lines = [line for line in text.splitlines() if " convolution(" in line
             and re.search(r'op_name="[^"]*[/(]%s[/)]' % scope, line)]
    return len(lines), sum("rematted_computation" in line for line in lines)


def attention_kernel_calls(by_op) -> Tuple[int, int, int]:
    """Calls of the block attention's (forward, dq, dk/dv) kernels among a
    compiled step's instructions (``profiling.hlo_op_scopes``' names:
    ``kernel`` or ``kernel.<n>``)."""
    called = collections.Counter(name.split(".")[0] for name in by_op)
    return tuple(called["splash_mqa_" + kernel] for kernel in (
        "fwd_residuals", "dq_no_residuals", "dkv_no_residuals"))


def all_eqns(jaxpr):
    """The equations of ``jaxpr`` and of every jaxpr inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from all_eqns(inner)


def cut_columns(a, first, n, heads, per):
    """Heads ``first .. first + n`` of ``heads`` along ``a``'s last axis,
    each ``per`` wide."""
    return a.reshape(*a.shape[:-1], heads, per)[
        ..., first:first + n, :].reshape(*a.shape[:-1], n * per)


def cut_rows(a, first, n, heads, per):
    return a.reshape(heads, per, -1)[first:first + n].reshape(n * per, -1)


KDA_BY_HEAD = ("kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k",
               "kda_conv_v", "kda_w_fb", "kda_w_gb", "kda_dt_bias")


def kda_head_share(lp, r, n, heads=8, per=8):
    """A layer's leaves with heads ``r n .. (r + 1) n`` of its KDA mixer's
    ``heads``."""
    out = {name: cut_columns(lp[name], r * n, n, heads, per)
           for name in KDA_BY_HEAD}
    out["kda_a_log"] = lp["kda_a_log"][r * n:(r + 1) * n]
    out["kda_w_b"] = lp["kda_w_b"][:, r * n:(r + 1) * n]
    out["kda_wo"] = cut_rows(lp["kda_wo"], r * n, n, heads, per)
    return {**lp, **out}


@dataclasses.dataclass(frozen=True)
class Spec:
    """One decoder, as the contract reads it."""
    #: the plain reference under ``benchmark/`` (read, never edited)
    ref: Any
    #: ``--model`` and its flags at small widths
    small: Dict[str, Any]
    #: the same widths under the reference's names
    sizes: Dict[str, Any]
    #: (change to the flags, what the refusal has to say)
    refusals: Tuple[Tuple[Dict[str, Any], str], ...]
    #: scopes the compiled step carries, and the rankers' it must not
    scopes: frozenset
    no_scopes: frozenset = frozenset({"fm", "tower", "cross", "bottom"})
    #: ``trainer -> step_notes`` the compiled step leaves (None: not held)
    notes: Optional[Callable[[Any], Dict[str, str]]] = None
    #: the shortest stack with every kind of layer: what the tests of a
    #: whole trainer step compile (the forward pass and the layers' order
    #: are held to the reference at ``small``'s depth)
    stack: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: rows of the table no sequence draws (SDAR's [MASK])
    reserved_rows: int = 0
    #: the loss draws noise from the step's key
    draws_noise: bool = False
    grad_tol: float = 1e-4
    logits_atol: float = 2e-5
    #: the model has expert layers (and their ``moe_*`` counts)
    experts: bool = True
    #: leaves (by their last name) whose gradient is zero by the mathematics:
    #: held to be small, and left out of the leaves' gaps (both sides hold
    #: rounding there)
    zero_gradient: Tuple[str, ...] = ()
    # --- HybridStack
    #: name -> (mixer, ffn) of each layer kind
    kinds: Dict[str, Tuple[str, str]] = dataclasses.field(
        default_factory=dict)
    #: count name -> the mixer or ffn whose layers report it
    layer_counts: Dict[str, str] = dataclasses.field(default_factory=dict)
    #: flags and reference sizes of the one-layer tests, over ``small``
    layer_flags: Dict[str, Any] = dataclasses.field(default_factory=dict)
    layer_sizes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: kind -> one layer's leaves, gains off one
    layer_leaves: Optional[Callable] = None
    #: the share test: the kinds it cuts, kind -> the uncut layer's leaves,
    #: (leaves, mixer, share) -> that head share's leaves, and the counts
    share_kinds: Tuple[str, ...] = ()
    share_leaves: Optional[Callable] = None
    head_share: Optional[Callable] = None
    head_shares: int = 1
    expert_shares: int = 1
    share_experts: int = 0
    shared_expert: bool = True
    # --- SmallBuffer, RowKernels
    small_buffer: int = 0
    #: flags over ``small``, id -> ``PASS_ROWS``, the notes' buffer rows
    row_kernels: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def V(self):
        return self.small["feature_size"]

    @property
    def L(self):
        return self.small["history_max_len"]

    @property
    def B(self):
        return self.small["batch_size"]

    def config(self, **kw):
        return Config(**{**self.small, "compute_dtype": "float32", **kw})

    def flat(self, params):
        """The program's parameter tree under the reference's names, the
        token table cut to the vocabulary's rows."""
        leaves, _ = jax.tree_util.tree_flatten_with_path(params)
        out = {_program.leaf_name(p): np.asarray(x) for p, x in leaves}
        out["tok_emb"] = out["tok_emb"][:self.V]
        return out

    def judged(self, tree):
        """``tree`` (flat) without the ``zero_gradient`` leaves."""
        return {k: v for k, v in tree.items()
                if k.rsplit(".", 1)[-1] not in self.zero_gradient}

    def sequences(self, n, seed):
        return np.random.default_rng(seed).integers(
            0, self.V - self.reserved_rows, (n, self.L)).astype(np.int32)


#: test -> (argument names, spec -> the cases): what a spec parametrises.
_CASES = {
    "test_config_says_plainly_what_the_model_does_not_take":
        ("change, says", lambda s: s.refusals),
    "test_a_layer_matches_the_reference": ("kind", lambda s: sorted(s.kinds)),
    "test_the_shares_add_up_to_the_uncut_layer":
        ("kind", lambda s: s.share_kinds),
    "test_model_by_the_row_kernels_takes_the_same_step":
        ("pass_most", lambda s: [
            pytest.param(rows, id=name)
            for name, rows in s.row_kernels["passes"].items()]),
}


class FromSpec:
    """A class of tests that reads its cases and its model from ``spec``:
    the base of the contract and of each mixin, so that a model's file may
    hold a mixin in a class (and a file) of its own."""
    spec: Spec

    def pytest_generate_tests(self, metafunc):
        case = _CASES.get(metafunc.function.__name__)
        if case is not None:
            metafunc.parametrize(case[0], case[1](metafunc.cls.spec))

    def key(self, seed):
        """The loss's key: a model that draws nothing takes none."""
        return jax.random.PRNGKey(seed) if self.spec.draws_noise else None

    def step_metrics_hold(self, metrics):
        """What a model asserts of every trainer step's metrics."""


class DecoderContract(FromSpec):
    """The tests all four decoders take."""

    # ------------------------------------------ what a model may say anew

    def reference_loss(self, params, tokens, state, rng):
        """(loss, logits) of the reference on the reference's leaves."""
        return self.spec.ref.forward_loss(params, tokens, self.spec.sizes)

    def start_state(self, trainer):
        return trainer.init_state(seed=3)

    def follower(self, start, state, learning_rate):
        return self.spec.ref.Follower(start, self.spec.sizes, learning_rate)

    def reference_inputs(self, tokens, base, step, n_dev):
        """``Follower.step``'s arguments for the step of ``tokens``; ``base``
        is the key the trainer's state started from."""
        return (tokens,)

    # ------------------------------------------------ built once a model

    def _seeded(self, cfg):
        model = get_model(cfg)
        params, state = model.init(jax.random.PRNGKey(0))
        return model, off_one(jax.random.PRNGKey(5), params), state

    @pytest.fixture(scope="class")
    def seeded(self):
        """(model, params with gains moved off one, state) at ``small``."""
        return self._seeded(self.spec.config())

    @pytest.fixture(scope="class")
    def short(self, seeded):
        """The same at ``stack``'s depth (``seeded`` itself where the
        spec cuts nothing)."""
        spec = self.spec
        return self._seeded(spec.config(**spec.stack)) if spec.stack \
            else seeded

    @pytest.fixture(scope="class")
    def program(self, compiled_once):
        """The float32 one-device trainer at ``stack``'s depth and its
        compiled step (``conftest.py``'s ``compiled_once``: the tests that
        run it and the test that asks the trainer for its scopes share one
        compilation)."""
        spec = self.spec
        trainer = trainer_on(1, spec.config(**spec.stack))
        return SimpleNamespace(trainer=trainer, step=compiled_once(trainer))

    @pytest.fixture(scope="class")
    def followed(self, program):
        """The reference's three steps from the trainer's own start on one
        device: what the float32 and the bfloat16 program are both held to
        (one start, one learning rate, one run of the follower)."""
        trainer = program.trainer
        state = self.start_state(trainer)
        return self.reference_steps(
            self.spec.flat(jax.tree.map(np.asarray, state.params)), state,
            trainer.cfg.learning_rate, 1)

    # ------------------------------------------------------- the helpers

    def reference_steps(self, start, state, learning_rate, n_dev):
        spec = self.spec
        base = jnp.asarray(np.asarray(state.rng))
        follower = self.follower(start, state, learning_rate)
        losses = [follower.step(*self.reference_inputs(
            spec.sequences(spec.B, 10 + step), base, step, n_dev))
            for step in range(STEPS)]
        return SimpleNamespace(start=start, mu=follower.mu,
                               params=follower.params, losses=losses)

    def follow(self, trainer, step, n_dev=1, reference=None):
        """``STEPS`` steps of ``trainer`` by ``step`` against the
        reference's follower -> (worst first-moment gap, worst
        parameter-change gap, [(loss, the reference's)], the last state).
        ``reference``: steps the follower already took from this start."""
        spec = self.spec
        state = self.start_state(trainer)
        start = spec.flat(jax.tree.map(np.asarray, state.params))
        if reference is None:
            reference = self.reference_steps(
                start, state, trainer.cfg.learning_rate * n_dev, n_dev)
        else:       # the same start, or the follower is not this run's
            assert all(np.array_equal(start[k], reference.start[k])
                       for k in reference.start)
        losses = []
        for i in range(STEPS):
            state, m = step(state, trainer.put_batch(
                batch_of(spec.sequences(spec.B, 10 + i))))
            losses.append(float(m["xent"]))
            self.step_metrics_hold(m)
        got = spec.judged(spec.flat(jax.tree.map(np.asarray, state.params)))
        mu = spec.judged(spec.flat(jax.tree.map(
            np.asarray, optax.tree_utils.tree_get(state.opt_state, "mu"))))
        return (worst_leaf_gap(mu, spec.judged(reference.mu))[0],
                worst_leaf_gap({k: got[k] - start[k] for k in got},
                               {k: reference.params[k] - start[k]
                                for k in got})[0],
                list(zip(losses, reference.losses)), state)

    def logits_and_loss(self, seeded):
        """The contract's comparison -> the forward pass's counts."""
        spec = self.spec
        model, params, state = seeded
        tokens = jnp.asarray(spec.sequences(spec.B, 0))
        key = self.key(7)

        # (jitted: op by op the reference's scans and maps take minutes)
        @jax.jit
        def program(p, s):
            logits, counts = model.apply(p, s, None, None, train=True,
                                         rng=key, hist_ids=tokens)
            per_seq, _ = model.per_example_loss(
                p, s, {"hist_ids": tokens}, train=True, rng=key)
            return logits, counts, per_seq

        logits, counts, per_seq = program(params, state)
        with highest():
            want_loss, want_logits = jax.jit(
                lambda p, s: self.reference_loss(p, tokens, s, key))(
                {k: jnp.asarray(v) for k, v in spec.flat(params).items()},
                state)
        assert logits.shape == (spec.B, spec.L, spec.V)
        np.testing.assert_allclose(logits, want_logits,
                                   atol=spec.logits_atol)
        np.testing.assert_allclose(jnp.mean(per_seq), want_loss, rtol=1e-6)
        if spec.experts:
            assert int(counts["moe_pairs_over_buffer"]) == 0
        return counts

    def gradients(self, short):
        """The contract's comparison -> the program's gradients, flat."""
        spec = self.spec
        model, params, state = short
        tokens = jnp.asarray(spec.sequences(spec.B, 1))
        key = self.key(8)

        def loss(p):
            per_seq, _ = model.per_example_loss(
                p, state, {"hist_ids": tokens}, train=True, rng=key)
            return jnp.mean(per_seq)

        got = spec.flat(jax.jit(jax.grad(loss))(params))
        with highest():
            want = jax.jit(jax.grad(lambda p: self.reference_loss(
                p, tokens, state, key)[0]))(
                {k: jnp.asarray(v) for k, v in spec.flat(params).items()})
        assert set(got) == set(want)
        largest = max(np.linalg.norm(g) for g in want.values())
        for name in want:
            if name not in spec.judged(want):       # zero: both are rounding
                assert np.linalg.norm(got[name]) < 1e-5 * largest, name
                continue
            assert leaf_gap(got[name], want[name]) < spec.grad_tol, name
            assert np.linalg.norm(want[name]) > 0, name
        return got

    def three_steps(self, n_dev, program, followed):
        """The contract's comparison -> the last state. float32 against
        float32: the losses to 1e-5, Adam's first moment to ``TOL``, the
        parameters' change to 2% (Adam divides by the gradient's own size:
        where a gradient is tiny its rounding decides the step's sign, and
        the change reads it)."""
        spec = self.spec
        if n_dev == 1:
            run = self.follow(program.trainer, program.step,
                              reference=followed)
        else:
            trainer = trainer_on(n_dev, spec.config(mesh_data=n_dev,
                                                    **spec.stack))
            run = self.follow(trainer, trainer.train_step, n_dev)
        mu_gap, change_gap, losses, state = run
        for got, want in losses:
            assert abs(got - want) < 1e-5 * max(1.0, abs(want))
        assert mu_gap < TOL
        assert change_gap < 0.02
        return state

    # --------------------------------------------------------- the tests

    def test_logits_and_loss_match_the_reference(self, seeded):
        self.logits_and_loss(seeded)

    def test_gradients_of_every_leaf_match_the_reference(self, short):
        self.gradients(short)

    @pytest.mark.parametrize("n_dev", [1, 2])
    def test_three_adam_steps_match_the_reference(self, n_dev, program,
                                                  followed):
        self.three_steps(n_dev, program, followed)

    def test_bfloat16_compute_misses_the_tolerance(self, followed):
        """bfloat16 products round an operand to 2^-8: ten times float32's
        band and more, so a step one precision lower is told apart."""
        spec = self.spec
        trainer = trainer_on(1, spec.config(compute_dtype="bfloat16",
                                            **spec.stack))
        mu_gap, change_gap, _, _ = self.follow(trainer, trainer.train_step,
                                               reference=followed)
        assert mu_gap > 10 * TOL and change_gap > 0.02

    def test_compiled_step_carries_each_blocks_scope(self, program):
        spec = self.spec
        scopes = set(program.trainer.step_op_scopes().values())
        assert spec.scopes <= scopes
        assert not spec.no_scopes & scopes
        if spec.notes is not None:
            assert program.trainer.model.step_notes == spec.notes(
                program.trainer)

    def test_config_says_plainly_what_the_model_does_not_take(self, change,
                                                              says):
        with pytest.raises(ValueError, match=says):
            self.spec.config(**change)


class HybridStack(FromSpec):
    """For ``KimiLinear`` and its subclasses: a stack of layer kinds, each
    ``_layer(mixer, ffn, x, leaves)`` against the reference's ``layer``."""

    def test_a_layer_matches_the_reference(self, kind):
        spec = self.spec
        model = get_model(spec.config(**spec.layer_flags))
        lp = spec.layer_leaves(spec.kinds[kind])
        x = 2.0 * jax.random.normal(jax.random.PRNGKey(1),
                                    (spec.B, spec.L, 32))
        got, counts = jax.jit(functools.partial(
            model._layer, *spec.kinds[kind]))(x, lp)
        sizes = {**spec.sizes, **spec.layer_sizes}
        with highest():
            want = jax.jit(lambda x, lp: spec.ref.layer(x, lp, sizes))(x, lp)
        np.testing.assert_allclose(got, want, atol=2e-5)
        for name, part in spec.layer_counts.items():
            assert (name in counts) == (part in spec.kinds[kind]), name

    def test_the_shares_add_up_to_the_uncut_layer(self, kind):
        """The configuration's layout at small widths: ``head_shares`` of a
        mixer and ``expert_shares`` of ``share_experts`` experts, top-4. The
        head shares' ``wo`` partial sums and the routed experts' partial
        sums added, the shared expert, the dense MLP and the residual stream
        counted once, are the uncut reference's layer."""
        spec = self.spec
        mixer, ffn = spec.kinds[kind]
        experts, eps = spec.share_experts, spec.sizes["eps"]
        held, pairs = experts // spec.expert_shares, 4 * spec.B * spec.L
        lp = spec.share_leaves(spec.kinds[kind])
        x = 2.0 * jax.random.normal(jax.random.PRNGKey(2),
                                    (spec.B, spec.L, 32))
        sizes = {**spec.sizes, "top_k": 4, "first_expert": 0}
        with highest():
            want = jax.jit(lambda x, lp: spec.ref.layer(x, lp, sizes))(x, lp)
        model = get_model(spec.config(
            moe_top_k=4, moe_experts=experts, moe_experts_held=held,
            moe_first_expert=0, moe_pair_capacity=pairs))
        # (a share's program is every share's: compiled once, run on each)
        mix = jax.jit(lambda sp: model._mixer(mixer, sp, x)[0])
        h = x + sum(mix(lp if spec.head_share is None
                        else spec.head_share(lp, mixer, r))
                    for r in range(spec.head_shares))
        routed = jax.jit(lambda sp, first: sdar_moe.expert_layer(
            sp, h, top_k=4, first_expert=first, capacity=pairs, eps=eps,
            cdt=F32, route_by=model.route_by))
        out, seen = h, 0
        if ffn == "moe":
            for first in range(0, experts, held):
                part, counts = routed(
                    {**lp, **{n: lp[n][first:first + held]
                              for n in ("w_gate", "w_up", "w_down")}}, first)
                out = out + part
                seen += int(counts["moe_pairs_held"])
            assert seen == pairs            # every pair, once
        if ffn == "mlp" or spec.shared_expert:      # on every chip: once
            out = out + kimi_linear.swiglu(
                lp, "mlp_" if ffn == "mlp" else "shared_", h, eps=eps,
                cdt=F32)
        np.testing.assert_allclose(out, want, atol=3e-5)

    def fit_from_shards(self, tmp_path):
        """``Trainer.fit`` over the normal file pipeline (the tokens ride
        the record's history list), one step a dispatch: the loss falls and
        the counts ride the metrics -> (the steps' metrics, the state)."""
        from deepfm_tpu.train import tasks

        spec = self.spec
        rng = np.random.default_rng(0)
        path = str(tmp_path / "tr-0.tfrecord")
        with tfrecord.TFRecordWriter(path) as w:
            for _ in range(16):
                # a sequence a model can learn: a walk of +1 from a random
                # start
                row = (rng.integers(0, spec.V) + np.arange(spec.L)) % spec.V
                w.write(example_codec.encode_ctr_example(
                    0.0, np.zeros(1), np.ones(1), hist_ids=row))
        # (1e-2: at the contract's 1e-3 the loss does not fall by 40% in
        # these 48 steps)
        cfg = spec.config(learning_rate=1e-2, log_steps=1000, **spec.stack)
        trainer = trainer_on(1, cfg)
        pipeline = tasks.make_pipeline(cfg, [path], epochs=6)
        seen = []
        try:
            state, out = trainer.fit(trainer.init_state(seed=0), pipeline,
                                     hooks=[lambda s, m: seen.append(m)])
        finally:
            pipeline.close()
        losses = [float(m["xent"]) for m in seen]
        assert len(losses) == 6 * 16 // spec.B
        assert losses[-1] < 0.6 * losses[0]
        assert np.isfinite(float(out["loss"]))
        if spec.experts:
            assert int(seen[-1]["moe_pairs_held"]) > 0
        return seen, state

    def test_fit_trains_from_tfrecord_shards(self, tmp_path):
        self.fit_from_shards(tmp_path)


class SmallBuffer(FromSpec):
    """For the models whose trainer is run at ``small_buffer`` pairs a
    layer."""

    def test_pairs_over_a_small_buffer_are_counted_not_lost(self):
        """A trainer's state keeps the run's total."""
        spec = self.spec
        trainer = trainer_on(1, spec.config(
            moe_pair_capacity=spec.small_buffer, **spec.stack))
        state = trainer.init_state(seed=1)
        seen = []
        for step in range(2):
            state, m = trainer.train_step(state, trainer.put_batch(
                batch_of(spec.sequences(spec.B, step))))
            seen.append(int(m["moe_pairs_over_buffer"]))
            self.step_metrics_hold(m)
        assert 0 < seen[0] < seen[1]
        assert int(state.model_state["moe_pairs_over_buffer"]) == seen[1]


def force_row_kernels(monkeypatch):
    """The expert layer's row kernels through the Pallas interpreter."""
    for name in ("gather", "combine"):
        monkeypatch.setattr(pallas_moe_rows, name, functools.partial(
            getattr(pallas_moe_rows, name), interpret=True))


class RowKernels(FromSpec):
    """For the models whose expert layers' rows are held to the row kernels
    in the whole model (``row_kernels``)."""

    def row_kernels_step(self, monkeypatch, pass_most):
        """The whole model with the expert layers' rows taken and added by
        the row kernels (``ops/pallas_moe_rows``, forced on through the
        Pallas interpreter at rows of one 128-lane line): loss, counts and
        every leaf's gradient against the XLA rows, in passes of at most
        ``pass_most`` rows, and the same again with the grouped products by
        their kernels; the notes say which moved and multiplied them ->
        pairs held."""
        spec = self.spec
        monkeypatch.setattr(sdar_moe, "PASS_ROWS", pass_most)
        cfg = spec.config(**spec.row_kernels["flags"])
        tokens = jnp.asarray(spec.sequences(spec.B, 3))
        key = self.key(2)

        def grads():
            model = get_model(cfg)
            params, state = model.init(jax.random.PRNGKey(0))

            def loss(p):
                per_seq, counts = model.per_example_loss(
                    p, state, {"hist_ids": tokens}, train=True, rng=key)
                return jnp.mean(per_seq), counts
            return model, jax.jit(jax.value_and_grad(loss, has_aux=True))(
                params)

        model, ((want, want_counts), want_g) = grads()
        assert model.step_notes["moe_rows"] == "xla"
        force_row_kernels(monkeypatch)
        monkeypatch.setattr(pallas_moe_rows, "supported",
                            lambda width, positions, rows, backend=None: True)
        model, ((got, got_counts), got_g) = grads()
        assert model.step_notes["moe_rows"] == "kernel"
        assert model.step_notes["moe_rows_moved"] == "{moe_pairs_held}/%d" % (
            spec.row_kernels["moved"])
        held = int(want_counts["moe_pairs_held"])

        def same_step():
            assert 0 < held == int(got_counts["moe_pairs_held"])
            np.testing.assert_allclose(got, want, rtol=1e-6)
            for (path, g), w in zip(
                    jax.tree_util.tree_leaves_with_path(got_g),
                    jax.tree.leaves(want_g)):
                np.testing.assert_allclose(
                    g, w, atol=2e-5, err_msg=jax.tree_util.keystr(path))

        same_step()
        # and with the grouped products made by the kernels that stop at the
        # valid prefix too (``ops/pallas_grouped_dot``, through the
        # interpreter, which leaves NaNs in the rows they do not write)
        assert model.step_notes["moe_products"] == "xla"
        monkeypatch.setattr(pallas_grouped_dot, "grouped_dot",
                            functools.partial(pallas_grouped_dot.grouped_dot,
                                              tile=16, interpret=True))
        monkeypatch.setattr(pallas_grouped_dot, "supported",
                            lambda rows, width, hidden, backend=None: True)
        model, ((got, got_counts), got_g) = grads()
        assert model.step_notes["moe_products"].startswith("kernel rows")
        same_step()
        return held

    def test_model_by_the_row_kernels_takes_the_same_step(self, monkeypatch,
                                                          pass_most):
        self.row_kernels_step(monkeypatch, pass_most)
