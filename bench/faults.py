"""Faults planted in the program's serving path, each of which a run's
check has to catch (``bench/tests/test_faults.py`` on the CPU,
``bench/control.py --fault`` on the chip):

* ``state_unchanged`` -- the decode step returns its state unchanged
  (the KV write is lost);
* ``half_batch`` -- half of the batch is left out (the second half of
  the slots is answered with the first half's tokens);
* ``token_altered`` -- a token is altered where it is produced (the
  sampler's output).

The fourth fault of the list, the exchange between chips left out,
needs a cell on several chips; the benchmark has none yet.  Each fault
is a context manager; plant it before the engine is built, since the
engine's programs are traced then.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(mod, name: str, value):
    old = getattr(mod, name)
    setattr(mod, name, value)
    try:
        yield
    finally:
        setattr(mod, name, old)


def state_unchanged():
    from repro.layers import attention

    return _patched(attention, "paged_cache_update",
                    lambda k, v, *a, **kw: (k, v))


def half_batch():
    from repro.serving import engine

    real = engine.sample_tokens

    def half(*a, **kw):
        toks = real(*a, **kw)
        n = toks.shape[0]
        return toks.at[n - n // 2:].set(toks[:n // 2]) if n > 1 else toks

    return _patched(engine, "sample_tokens", half)


def token_altered():
    from repro.serving import engine

    real = engine.sample_tokens
    return _patched(engine, "sample_tokens",
                    lambda *a, **kw: (real(*a, **kw) + 1) % 4096)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
