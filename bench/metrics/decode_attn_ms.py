"""Device time of attention in one decode step, in ms: self time of the ops
under ``attn.*`` scopes (rope, K/V write into the pool, paged gather,
scores to values) inside the decode program's executions on device 0, per
execution (attention and KV pool)."""
from bench import scopes

MODULE = "jit_step_fn"


def read(run):
    return scopes.scoped_ms(run, MODULE, "attn.")
