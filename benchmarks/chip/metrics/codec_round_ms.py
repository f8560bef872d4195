"""Device time per step of the codec round: the operations under the
program's ``grad_sync``/``codec`` scope (``scopes.py``), averaged over the
cell's devices. Needs ``Reading.scopes``, the compiled step's scope map."""

from benchmarks.chip import scopes


def read(r):
    return scopes.per_step_ms(r, scopes.CODEC)
