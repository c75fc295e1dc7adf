"""Faults planted under a run, for the tests and for reading their numbers
on the chip (``chip_faults.py``)."""

from __future__ import annotations


def label_one_round_short():
    """The device labeler stopped one propagation round before its fixed
    point: the same threshold, dilation and min-label propagation as
    ``grid_engine._label_group``, returning the labels of the round before
    the last one that changed any."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def label(diff, eff):
        s, f, gh, gw = diff.shape
        mask = diff > eff[:, :, None, None]
        fr = jnp.zeros_like(mask[:, :, :1, :])
        fc = jnp.zeros_like(mask[:, :, :, :1])
        m = mask
        m = m | jnp.concatenate([fr, mask[:, :, :-1, :]], axis=2)
        m = m | jnp.concatenate([mask[:, :, 1:, :], fr], axis=2)
        m = m | jnp.concatenate([fc, mask[:, :, :, :-1]], axis=3)
        m = m | jnp.concatenate([mask[:, :, :, 1:], fc], axis=3)
        big = gh * gw
        iota = jnp.arange(big, dtype=jnp.int32).reshape(gh, gw)
        mm = m.reshape(s * f, gh, gw)
        ids0 = jnp.where(mm, iota[None], big)
        big_row = jnp.full((s * f, 1, gw), big, jnp.int32)
        big_col = jnp.full((s * f, gh, 1), big, jnp.int32)
        pad_tail = jnp.full((s * f, 1), big, jnp.int32)

        def prop(ids):
            up = jnp.concatenate([big_row, ids[:, :-1, :]], axis=1)
            down = jnp.concatenate([ids[:, 1:, :], big_row], axis=1)
            left = jnp.concatenate([big_col, ids[:, :, :-1]], axis=2)
            right = jnp.concatenate([ids[:, :, 1:], big_col], axis=2)
            n = jnp.minimum(jnp.minimum(jnp.minimum(ids, up), down),
                            jnp.minimum(left, right))
            n = jnp.where(mm, n, big)
            flat = jnp.concatenate([n.reshape(s * f, -1), pad_tail], axis=1)
            jumped = jnp.take_along_axis(
                flat, n.reshape(s * f, -1), axis=1).reshape(n.shape)
            return jnp.where(mm, jnp.minimum(n, jumped), big)

        def cond(c):
            return jnp.any(c[0] != c[1])

        def body(c):
            return prop(c[0]), c[0], c[1]

        _, _, before = jax.lax.while_loop(cond, body,
                                          (prop(ids0), ids0, ids0))
        return before.reshape(s, f, gh, gw)

    return label


def plant_label_one_round_short(cell=None) -> None:
    from repro.core import grid_engine

    grid_engine._label_group = label_one_round_short()


def plant_blank_payloads(cell=None) -> None:
    """Every frame served blank (all zero bytes) where the camera node
    produces it."""
    import dataclasses

    import numpy as np
    from repro.core import broker

    orig = broker.CamBroker.fetch

    def fetch(self, *a, **k):
        out = orig(self, *a, **k)
        return [dataclasses.replace(d, frame=np.zeros_like(d.frame))
                if d.frame is not None else d for d in out]
    broker.CamBroker.fetch = fetch
