"""Device time of the factorized linears in one decode step, in ms: self
time of the ops under ``fact.*`` scopes inside the decode program's
executions on device 0, per execution (factorized linears)."""
from bench import scopes

MODULE = "jit_step_fn"


def read(run):
    return scopes.scoped_ms(run, MODULE, "fact.")
