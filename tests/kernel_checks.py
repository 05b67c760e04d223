"""What a model's tests and its kernels' tests both use: distances, and the
kernels of a traced function."""

import collections

import jax
import numpy as np


def rel_l2(got, want) -> float:
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def leaves_close(got, want, limit):
    want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        assert np.linalg.norm(np.asarray(want[path], np.float64)) > 0, path
        assert rel_l2(g, want[path]) < limit, jax.tree_util.keystr(path)


def equations_of(jaxpr):
    """Every equation of ``jaxpr``, nested jaxprs included (a loop's body
    once)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for j in v if isinstance(v, (list, tuple)) else (v,):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from equations_of(j)


def equations(fn, *args):
    """Every equation of ``fn``'s jaxpr (:func:`equations_of`)."""
    return list(equations_of(jax.make_jaxpr(fn)(*args).jaxpr))


def kernel_counts(fn, *args):
    return dict(collections.Counter(
        e.params["name"] for e in equations(fn, *args)
        if e.primitive.name == "pallas_call"))


def sala_hyper(cfg):
    """``benchmark/reference/minicpm_sala.py``'s view of a program
    configuration (``models/minicpm_sala.py:MiniCPMSALAConfig``)."""
    from benchmark.reference import minicpm_sala as ref
    return ref.Hyper(
        n_head=cfg.num_attention_heads, n_kv_head=cfg.num_key_value_heads,
        lightning_heads=cfg.lightning_nh, mixer_types=cfg.mixer_types,
        first_layer=cfg.first_layer, published_layers=cfg.published_layers,
        scale_emb=cfg.scale_emb, scale_depth=cfg.scale_depth,
        dim_model_base=cfg.dim_model_base, rope_theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps, **cfg.sparse._asdict())


def inverses_side_by_side(inverse, A, narrow):
    """``inverse`` (``_delta_rule._inverse``) of the list ``A`` of strictly
    lower ``[C, C]`` systems: a list, each entry bit for bit the call's on
    that system alone, strictly lower beside its diagonal of ones, and the
    inverse of ``I + A``."""
    both = inverse(A, narrow)
    assert isinstance(both, list) and len(both) == len(A)
    eye = np.eye(A[0].shape[0])
    for a, inv in zip(A, both):
        np.testing.assert_array_equal(np.asarray(inv),
                                      np.asarray(inverse(a, narrow)))
        back = np.asarray(inv, np.float64) @ (eye + np.asarray(a, np.float64))
        assert rel_l2(back, eye) < 3e-6
        assert not np.triu(np.asarray(inv), 1).any()
