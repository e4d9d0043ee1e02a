"""Region-inference cases: hazards far from the jit decorator.

The analyzer must carry tracedness through project-internal calls,
``lax.while_loop`` bodies, nested defs, and ``shard_map`` closures —
and static-param declarations must propagate along the same edges.
"""

import functools

import jax
import jax.numpy as jnp


def helper_called_from_jit(x, mode):
    # traced transitively (entry -> helper); mode arrives static
    if mode == "dense":  # static at every traced call site: quiet
        x = x * 2
    assert (x > 0).all()  # expect: TS01
    return x


def loop_body(carry):
    x, i = carry
    if x.sum() > 0:  # expect: TS02
        x = x - 1
    return x, i + 1


def loop_cond(carry):
    x, i = carry
    return i < 8


@functools.partial(jax.jit, static_argnames=("mode",))
def entry(x, *, mode):
    x = helper_called_from_jit(x, mode)
    x, _ = jax.lax.while_loop(loop_cond, loop_body, (x, jnp.int32(0)))

    def nested(y):
        return float(y[0])  # expect: TS03

    return nested(x)


def make_sharded(mesh, spec):
    scale = 2.0  # closure var from host scope: static inside body

    def body(x):
        if scale > 1.0:  # host closure value: quiet
            x = x * scale
        assert (x > 0).all()  # expect: TS01
        return x

    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )


def plain_helper(x, mode):
    # identical shape to helper_called_from_jit but never reachable from
    # a trace root — the analyzer must leave host code alone
    if x.sum() > 0:
        x = x + 1
    assert (x > 0).all()
    return float(x[0]) if mode == "dense" else 0.0
