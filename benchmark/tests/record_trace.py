"""Records the small trace that test_trace_reduce.py checks the reduction
against: a few jitted products on one TPU, with two `bench:` host spans and a
deliberate host sleep between them. Run on the chip; writes
chiprun_out/small_trace.xplane.pb (copy it to benchmark/tests/)."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
out = os.path.join(ROOT, "chiprun_out")
tmp = os.path.join(out, "small_trace_dir")
os.makedirs(out, exist_ok=True)
if jax.devices()[0].platform != "tpu":
    sys.exit("record_trace: needs a TPU")
x = jnp.ones((2048, 2048), jnp.bfloat16)
f = jax.jit(lambda x: (x @ x) * jnp.bfloat16(1e-3))
f(x).block_until_ready()
jax.profiler.start_trace(tmp)
with jax.profiler.TraceAnnotation("bench:dispatch"):
    y = x
    for _ in range(4):
        y = f(y)
    y.block_until_ready()
with jax.profiler.TraceAnnotation("bench:data_wait"):
    time.sleep(0.02)
with jax.profiler.TraceAnnotation("bench:dispatch"):
    f(y).block_until_ready()
jax.profiler.stop_trace()
src, = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
shutil.copy(src, os.path.join(out, "small_trace.xplane.pb"))
shutil.rmtree(tmp)
print("small trace bytes", os.path.getsize(os.path.join(out, "small_trace.xplane.pb")))
