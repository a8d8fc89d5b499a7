"""Micro-probes for the r5 ResNet findings:
  1. per-channel reduction of [128,56,56,256] bf16: jnp.mean vs ones-dot
  2. 1x1 wgrad: XLA autodiff's reduce-fusion form vs explicit dot_general
Calibrated scan harness.
"""

import time

import jax
import jax.numpy as jnp
from jax import lax

PEAK = 197e12

_OVERHEAD = None


def overhead():
    global _OVERHEAD
    if _OVERHEAD is None:
        z = jnp.zeros((8, 128), jnp.float32)

        @jax.jit
        def trivial(z):
            y, _ = lax.scan(lambda c, _: (c + 1.0, ()), z, None, length=4)
            return jnp.sum(y)

        float(trivial(z))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(trivial(z))
            best = min(best, time.perf_counter() - t0)
        _OVERHEAD = best
        print(f"calibrated sync overhead: {best*1000:.1f} ms", flush=True)
    return _OVERHEAD


def timeit(name, fn, args, reps, work_desc):
    @jax.jit
    def loop(*args):
        def step(c, _):
            r = fn(*((c,) + args[1:]))
            # chain: perturb carry by a scalar derived from r
            s = jnp.sum(jax.tree.leaves(r)[0].astype(jnp.float32))
            return c + (s * 1e-20).astype(c.dtype), ()
        y, _ = lax.scan(step, args[0], None, length=reps)
        return jnp.sum(y.astype(jnp.float32))

    float(loop(*args))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        float(loop(*args))
        best = min(best, time.perf_counter() - t0)
    dt = max(best - overhead(), 1e-9) / reps
    print(f"{name:56s} {dt*1000:8.3f} ms   ({work_desc})", flush=True)
    return dt


def main():
    overhead()
    key = jax.random.PRNGKey(0)
    B, H, C = 128, 56, 256
    x = jax.random.normal(key, (B, H, H, C), jnp.bfloat16)
    dy = jax.random.normal(key, (B, H, H, C), jnp.bfloat16)
    GB = B * H * H * C * 2 / 1e9

    timeit("mean+meansq reduce (jnp, f32 acc)",
           lambda x: (jnp.mean(x, (0, 1, 2), dtype=jnp.float32),
                      jnp.mean(jnp.square(x.astype(jnp.float32)), (0, 1, 2))),
           (x,), 200, f"{GB:.2f} GB read; roofline ~{GB/819*1000:.2f} ms")

    ones = jnp.ones((B * H * H,), jnp.bfloat16)

    def dot_stats(x, ones):
        x2 = x.reshape(-1, C)
        m = lax.dot_general(ones, x2, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        m2 = lax.dot_general(ones, jnp.square(x2), (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return m, m2

    timeit("mean+meansq as ones-dot", dot_stats, (x, ones), 200,
           f"{GB:.2f} GB read")

    # wgrad 1x1: [BHW, 64] x [BHW, 256]
    cin = 64
    xs = jax.random.normal(key, (B * H * H, cin), jnp.bfloat16)
    dys = dy.reshape(-1, C)
    FL = 2 * B * H * H * cin * C

    def wgrad_dot(xs, dys):
        return lax.dot_general(xs, dys, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    timeit("1x1 wgrad as dot_general [64,256]", wgrad_dot, (xs, dys), 200,
           f"{FL/1e9:.1f} GF; {FL/1e9/197:.3f} ms at peak")

    def wgrad_autodiff(xs, dys):
        def f(w):
            y = (xs.reshape(B, H, H, cin))
            y = lax.conv_general_dilated(
                y, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return jnp.sum(y.reshape(-1, C) * dys.astype(y.dtype))
        return jax.grad(f)(jnp.zeros((1, 1, cin, C), jnp.bfloat16))

    timeit("1x1 wgrad via autodiff-of-conv", wgrad_autodiff, (xs, dys), 100,
           f"{FL/1e9:.1f} GF")

    # BN bwd reductions: sum(dy) and sum(dy*x) per channel
    def bnbwd_reduce(x, dy):
        return (jnp.sum(dy, (0, 1, 2), dtype=jnp.float32),
                jnp.sum((dy * x).astype(jnp.float32), (0, 1, 2)))

    timeit("BN-bwd sums (jnp reduce)", bnbwd_reduce, (x, dy), 200,
           f"{2*GB:.2f} GB read; roofline ~{2*GB/819*1000:.2f} ms")

    def bnbwd_dot(x, dy):
        dy2 = dy.reshape(-1, C)
        s1 = lax.dot_general(ones, dy2, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        s2 = lax.dot_general(x.reshape(-1, C) * dy2, ones,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return s1, s2

    timeit("BN-bwd sums as ones-dot", bnbwd_dot, (x, dy), 200,
           f"{2*GB:.2f} GB read")

    # elementwise roofline reference: y = a*x + b
    timeit("elementwise x*2+1 (read+write)",
           lambda x: x * jnp.bfloat16(2.0) + jnp.bfloat16(1.0), (x,), 200,
           f"{2*GB:.2f} GB r+w; roofline ~{2*GB/819*1000:.2f} ms")


if __name__ == "__main__":
    main()
