"""Faults planted under the timed path, to show that the comparison sees
them: the control's readings (``bench/calibrate.py``) and the tests.

* ``frozen_state``    each chunk returns the state it was given;
* ``half_batch``      the model loss takes the mean over the first half of
                      each minibatch and leaves the rest out;
* ``altered_answer``  one selection bit of each chunk's stream is flipped
                      where the stream is produced.

A single chip has no exchange between chips, so that fault does not apply.
"""
from __future__ import annotations

import jax


class _Wrapped:
    def __init__(self, engine, chunk):
        self._engine, self._chunk = engine, chunk

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def chunk(self, carry, ts, k_cap=None):
        return self._chunk(self._engine, carry, ts)


def frozen_state(engine):
    return _Wrapped(engine, lambda e, carry, ts: (carry, e.chunk(carry, ts)[1]))


_flip = jax.jit(lambda words: words.at[0, 0].set(words[0, 0] ^ 1))


def altered_answer(engine):
    def chunk(e, carry, ts):
        carry, out = e.chunk(carry, ts)
        return carry, out._replace(sel_mask=_flip(out.sel_mask))
    return _Wrapped(engine, chunk)


def half_batch(loss):
    def half(params, batch):
        return loss(params, {name: a[:a.shape[0] // 2]
                             for name, a in batch.items()})
    return half


ENGINE_FAULTS = {"frozen_state": frozen_state, "altered_answer": altered_answer}
LOSS_FAULTS = {"half_batch": half_batch}
