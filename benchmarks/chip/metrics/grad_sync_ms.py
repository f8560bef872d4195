"""Device time per step of the bucketed gradient sync: the operations
under the program's ``grad_sync`` scope (``scopes.py``; pack, exact
pmeans, codec round, unpack), averaged over the cell's devices. Needs
``Reading.scopes``, the compiled step's scope map."""

from benchmarks.chip import scopes


def read(r):
    return scopes.per_step_ms(r, scopes.SYNC)
