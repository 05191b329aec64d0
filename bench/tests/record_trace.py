"""Record the small profiler trace that bench/tests/test_trace.py reduces.

    python bench/tests/record_trace.py <out_dir>

On a TPU: a short program with one Pallas kernel named like the
benchmark's kernels and a matmul, run three times inside ``bench.step``
host spans with a host-side pause between, so the trace holds device ops,
a named kernel and labelled idle gaps.  The .xplane.pb is written under
``<out_dir>``.
"""
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def main(out: str) -> None:
    x = jnp.ones((1024, 1024), jnp.float32)
    kern = pl.pallas_call(_kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype), name="sla2_sparse_fwd_int8")
    f = jax.jit(lambda a: kern(a @ a))
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.submit"):
            time.sleep(0.002)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
