"""What every model's file asks of its model, once: the program against the
plain float32 reference (logits, loss, every leaf's gradient) in each layout,
and two steps through ``plan_training`` against a plain loop. A model is a
:class:`Model` row in its own file; the file keeps its tests' names and
parametrisation and hands the row to :func:`match_the_reference` /
:func:`two_planned_steps`, then asserts what only that model has (a bias's
counts, a router's state).

The row is also the file's holder of compiled programs. A program is traced
and compiled once a (function, configuration, shapes) and a file: every case
that wants ``loss_fn``'s value and gradient takes ``model.loss_and_grads``,
every gradient-accumulation step ``model.ga_step(cfg, micro)``. The
reference is traced once a model and run once a batch, always over the
``l{i}`` dicts' values as its own ``layers`` list: the layouts and the
rematerialised variants of the program are held to the same numbers.
"""

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from kernel_checks import leaves_close, rel_l2

from tepdist_tpu.optim import make_optimizer
from tepdist_tpu.parallel.sync_free import build_ga_step

KEY = jax.random.PRNGKey(0)


def tree_close(got, want, rtol=2e-5, skip=("router_bias",)):
    """Every leaf of ``got`` within ``rtol`` of the largest entry of its
    counterpart in ``want``; a selection bias's "gradient" is its step's
    counts and is left to the file."""
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        if any(s in jax.tree_util.keystr(path) for s in skip):
            continue
        w = np.asarray(flat_want[path])
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=0, atol=rtol * (np.abs(w).max() + 1e-12),
            err_msg=jax.tree_util.keystr(path))


rel_l2_close = functools.partial(leaves_close, limit=2e-5)


@dataclasses.dataclass(eq=False)
class Model:
    """A model's row. ``module``: ``tepdist_tpu.models.<name>``; ``ref``:
    ``benchmark.reference.<name>``; ``cfg``: the tests' preset; ``hyper``:
    a configuration as the reference's ``Hyper``; ``outside``: the leaves
    outside the layers; ``stack``: an ``l{i}`` tree of ``cfg`` in the stacked
    layout. The rest is what the comparison is made on and how close it must
    come: ``uneven`` moves gains and biases off their initial values,
    ``batch`` is (sequences, positions) of the compared batch, ``chunk`` the
    chunked loss's positions in a rematerialised variant, ``logits_atol`` an
    absolute distance, of the logits' largest entry if ``logits_relative``,
    ``close`` the gradients' ruler."""
    module: Any
    ref: Any
    cfg: Any
    hyper: Callable
    outside: Tuple[str, ...]
    stack: Callable
    uneven: Callable = staticmethod(lambda params: params)
    init: Optional[Callable] = None         # (cfg, key) -> ``l{i}`` dicts
    batch: Tuple[int, int] = (2, 32)
    chunk: int = 16
    logits_atol: float = 2e-5
    logits_relative: bool = False
    close: Callable = staticmethod(tree_close)
    opt: Optional[dict] = None              # the steps' optimizer

    def __post_init__(self):
        m, ref = self.module, self.ref
        # Each traced once a (configuration, shapes) and a file.
        self.loss_and_grads = jax.jit(jax.value_and_grad(m.loss_fn),
                                      static_argnums=2)
        self.loss_of = jax.jit(m.loss_fn, static_argnums=2)

        def with_logits(p, t, cfg):
            return m.loss_fn(p, t, cfg), m.forward(p, t[:, :-1], cfg)

        # ((loss, logits), gradients): one program where a case wants all
        # three.
        self.all_three = jax.jit(jax.value_and_grad(with_logits,
                                                    has_aux=True),
                                 static_argnums=2)
        self.ref_logits = jax.jit(lambda p, t, hp: ref.logits(p, t, hp),
                                  static_argnums=2)
        self.ref_loss = jax.jit(
            lambda p, t, hp, weights=None: ref.loss(p, t, hp,
                                                    weights=weights),
            static_argnums=2)
        self.ref_loss_and_grads = jax.jit(
            jax.value_and_grad(lambda p, t, hp: ref.loss(p, t, hp)),
            static_argnums=2)
        if hasattr(ref, "expert_counts"):
            self.ref_expert_counts = jax.jit(
                lambda p, t, hp: ref.expert_counts(p, t, hp),
                static_argnums=2)
        self._steps, self._made = {}, {}

    # -- parameters, views ----------------------------------------------------

    def variant(self, remat, cfg=None):
        """``cfg`` plain, or rematerialised under the chunked loss."""
        return dataclasses.replace(cfg or self.cfg, remat=bool(remat),
                                   loss_chunk=self.chunk if remat else 0)

    def init_params(self, cfg=None, stacked=False):
        """``cfg``'s parameters from ``KEY``, made once a preset (its sizes
        and dtype) and layout. Shared: whoever donates them takes a copy."""
        cfg = self.variant(False, cfg)
        if (cfg, stacked) not in self._made:
            flat = (self.init or self.module.init_params)(cfg, KEY)
            self._made[cfg, False] = flat
            self._made[cfg, True] = self.stack(flat, cfg)
        return self._made[cfg, stacked]

    def uneven_params(self, stacked):
        """``cfg``'s uneven parameters, the same values in either layout."""
        if ("uneven", stacked) not in self._made:
            flat = self.uneven(self.init_params())
            self._made["uneven", False] = flat
            self._made["uneven", True] = self.stack(flat, self.cfg)
        return self._made["uneven", stacked]

    def tokens(self, T=None):
        B, T0 = self.batch
        return self.module.fake_batch(self.cfg, B, T or T0, seed=1)

    def to_reference(self, params, cfg=None):
        """The reference's view of the program's parameters: the ``l{i}``
        dicts as its ``layers`` list (a stacked tree as it is: the
        reference reads either)."""
        if "l0" not in params:
            return params
        out = {k: params[k] for k in self.outside}
        out["layers"] = [params[f"l{i}"] for i in range(
            (cfg or self.cfg).num_hidden_layers)]
        return out

    def from_reference(self, tree):
        """The reference's ``layers`` list as the program's ``l{i}`` dicts."""
        out = {k: tree[k] for k in self.outside}
        out.update({f"l{i}": blk for i, blk in enumerate(tree["layers"])})
        return out

    def reference(self, what="loss", T=None):
        """Of ``uneven_params`` on ``tokens(T)``, the reference's ``"loss"``
        (with its gradients as ``l{i}`` dicts), ``"logits"`` or routers'
        ``"counts"``: the reference run once a batch."""
        if (what, T) not in self._made:
            view, hp = self.to_reference(self.uneven_params(False)), \
                self.hyper(self.cfg)
            tokens = self.tokens(T)
            if what == "logits":
                made = self.ref_logits(view, tokens[:, :-1], hp)
            elif what == "counts":
                made = self.ref_expert_counts(view, tokens, hp)
            else:
                loss, grads = self.ref_loss_and_grads(view, tokens, hp)
                made = loss, self.from_reference(grads)
            self._made[what, T] = made
        return self._made[what, T]

    def logits(self, params, tokens, cfg):
        """``forward`` on ``tokens[:, :-1]`` out of ``all_three``: the
        program a case that compared the logits compiled already."""
        return self.all_three(params, tokens, cfg)[0][1]

    # -- steps ------------------------------------------------------------------

    def step_fn(self, cfg, micro, opt=None, fused=True):
        """``(optimizer, gradient-accumulation step)`` of ``micro`` micro
        batches, a new function each call: for a case that traces it for its
        gauges or kernels, or under a patched module (a trace of a function
        traced before is served from ``jit``'s cache)."""
        tx = make_optimizer(dict(opt or self.opt))
        loss = lambda p, t: self.module.loss_fn(p, t, cfg)  # noqa: E731

        def apply_fn(p, s, g):
            updates, s = tx.update(g, s, p)
            return optax.apply_updates(p, updates), s

        return tx, build_ga_step(
            lambda p, t: jax.value_and_grad(loss)(p, t), apply_fn, micro,
            **({"loss_fn": loss} if fused else {}))

    def ga_step(self, cfg, micro, opt=None, fused=True):
        """``(optimizer, jitted step)`` of ``micro`` micro batches, one a
        (configuration, micro, optimizer) and a file: ``micro`` 1 is
        ``jax.value_and_grad`` of the whole batch and the optimizer, the
        plain loop."""
        key = (cfg, micro, tuple(sorted((opt or self.opt).items())), fused)
        if key not in self._steps:
            tx, step = self.step_fn(cfg, micro, opt, fused)
            self._steps[key] = tx, jax.jit(step)
        return self._steps[key]


def match_the_reference(model: Model, stacked, remat, *, logits=True, T=None):
    """The program in one layout, plain or rematerialised under the chunked
    loss, against the float32 reference at the highest precision: the logits
    (where ``logits``), the loss to 1e-5 and every leaf's gradient by the
    model's ruler, in the layout's own leaves. One program a variant, the
    reference's two once a model. Returns the program's gradients."""
    cfg = model.variant(remat)
    params, tokens = model.uneven_params(stacked), model.tokens(T)
    want_loss, want = model.reference("loss", T)
    if logits:
        (loss, got), grads = model.all_three(params, tokens, cfg)
        want_logits = model.reference("logits", T)
        scale = float(jnp.abs(want_logits).max()) \
            if model.logits_relative else 1.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want_logits),
                                   rtol=0, atol=model.logits_atol * scale)
    else:
        loss, grads = model.loss_and_grads(params, tokens, cfg)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    model.close(grads, model.stack(want, cfg) if stacked else want)
    return grads


def bf16_near_the_reference(model: Model, cfg, tokens, *, flat=None,
                            limit=0.05):
    """``cfg`` (a bf16 preset, rematerialised under the chunked loss) in the
    stacked layout against the reference on the same bf16 values, widened
    and as a list of layers (the program it is compiled for already): the
    loss to 2e-3, the leaves outside the layers to ``limit`` by relative L2
    (bf16's rounding of every activation through the layers: a per cent,
    not the float32 cases' 1e-5). Returns the program's gradients."""
    flat = model.init_params(cfg) if flat is None else flat
    loss, grads = model.loss_and_grads(model.stack(flat, cfg), tokens, cfg)
    wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), flat)
    want_loss, want = model.ref_loss_and_grads(
        model.to_reference(wide, cfg), tokens, model.hyper(cfg))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-3)
    for k in model.outside:
        assert rel_l2(grads[k], want[k]) < limit, k
    return grads


def two_planned_steps(model: Model, stacked, devices, *, plain_stacked=None,
                      uneven=False, each=None):
    """``plan_training`` with 2 micro batches accumulated in one program
    against ``jax.value_and_grad`` of the whole batch and the optimizer (the
    one-micro-batch step, compiled once a file and layout): the same losses.
    The plain loop runs over the ``plain_stacked`` layout (the plan's own
    where None); ``each(params, tokens, cfg)`` is called before each plain
    step. Returns (the plan's parameters, the loop's) for the file's ruler."""
    from tepdist_tpu.train import plan_training
    cfg = model.variant(True)
    plain_stacked = stacked if plain_stacked is None else plain_stacked
    params_of = model.uneven_params if uneven else \
        functools.partial(model.init_params, None)
    batches = [model.module.fake_batch(cfg, 4, 32, seed=s) for s in (2, 3)]
    tx, plain = model.ga_step(cfg, 1)
    # The plan's first step donates the arrays it was given.
    plan = plan_training(lambda p, t: model.module.loss_fn(p, t, cfg), tx,
                         jax.tree_util.tree_map(jnp.copy, params_of(stacked)),
                         batches[0], devices=devices[:1], explore=False,
                         num_micro_batches=2)
    p = jax.tree_util.tree_map(jnp.copy, params_of(plain_stacked))
    state = tx.init(p)
    for tokens in batches:
        if each is not None:
            each(p, tokens, cfg)
        want_loss, p, state = plain(p, state, tokens)
        assert plan.step(tokens) == pytest.approx(float(want_loss), rel=2e-6)
    got, _ = jax.tree_util.tree_unflatten(plan._state_tree,
                                          plan._device_state())
    return got, p
