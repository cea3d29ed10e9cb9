"""Faults planted under the timed path, for the tests that show a broken
program comes out as not correct.  Each takes the engine right after it is
built, before anything is compiled.  ``FAULTS`` are the faults of any
serving cell; ``FAULTS_TP`` those of a cell on a tensor-parallel mesh."""
from __future__ import annotations


def token_altered(engine) -> None:
    """Every sampled token is moved to its neighbour in the vocabulary,
    where it is produced (inside the compiled sampler)."""
    runner = engine.core.executor.runner
    sample, vocab = runner._sample, engine.cfg.vocab_size

    def altered(*args):
        return (sample(*args) + 1) % vocab

    runner._sample = altered


def state_unchanged(engine) -> None:
    """The decode step returns the KV pool it was given: no decoded
    token's K/V is ever written."""
    runner = engine.core.executor.runner
    step = runner._step

    def stale(params, data, *rest):
        nxt, _ = step(params, data, *rest)
        return nxt, data

    runner._step = stale


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged}


def exchange_dropped(engine) -> None:
    """Tensor parallelism without its exchange: the attention output
    projection, whose rows are split over the model axis, returns each
    chip's partial product as if it were the whole sum."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import factorized
    from repro.parallel import context as pctx

    if engine.mesh is None:
        raise ValueError("exchange_dropped needs a mesh")
    call = factorized.Linear.__call__

    def partial_only(self, params, x):
        mesh = pctx.current_mesh()
        if self.site != "attn_out" or mesh is None or "w" not in params:
            return call(self, params, x)
        lead = (None,) * (x.ndim - 1)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P("model", None), P(*lead, "model")),
                           out_specs=P(*lead, None), check_vma=False)
        def local(w, x):
            return x @ w
        return local(params["w"], x)

    factorized.Linear.__call__ = partial_only  # for the rest of the process


FAULTS_TP = {"exchange_dropped": exchange_dropped}
