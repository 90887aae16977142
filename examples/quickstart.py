"""Quickstart: the cf4ocl workflow on JAX, end to end in ~40 lines.

Context → queue → program (build/lower/compile) → kernel → buffers →
profiled dispatch → summary.  Mirrors the paper's Listing S2 skeleton.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp

from repro.core import Buffer, Context, DispatchQueue, ErrBox, Program, memcheck
from repro.core.compile_cache import use_compile_cache
from repro.prof import Prof, queue_chart

use_compile_cache()
err = ErrBox()

# Context over the devices of the backend JAX started on  (ccl_context_new_gpu)
ctx = Context.new_accel(err=err)
err.check()
dev = ctx.device(0)
print(f"* Device: {dev.name} (target: {dev.target_spec.name})")

# Command queue with profiling  (ccl_queue_new)
queue = DispatchQueue(ctx, "Main", profiling=True)

# Program: build a step function  (ccl_program_new + build)
prog = Program(ctx, lambda x, w: jnp.tanh(x @ w).sum(), name="tanh_matmul")
prog.build(err=err)
err.check()
kernel = prog.get_jit_kernel()

# Buffers  (ccl_buffer_new)
x = Buffer.new(ctx, (512, 512), jnp.float32, fill=0.5, err=err)
w = Buffer.new(ctx, (512, 512), jnp.float32, fill=0.01, err=err)
err.check()

# Profiled dispatch  (ccl_kernel_set_args_and_enqueue_ndrange)
prof = Prof()
prof.start()
for i in range(5):
    out = kernel.enqueue(queue, x.array, w.array, name="TANH_MATMUL")
queue.finish()
prof.stop()

# Profiling summary  (ccl_prof_get_summary — paper Fig. 3)
prof.add_queue("Main", queue)
prof.calc()
print(prof.get_summary())
print(queue_chart(prof, width=72))

# Lifecycle hygiene  (ccl_wrapper_memcheck)
for wrp in (x, w, kernel, prog, queue, ctx):
    wrp.destroy()
print("memcheck (context objects):",
      "PASS" if all(v == 0 or k in ("Device", "Platform", "Event")
                    for k, v in __import__("repro.core", fromlist=["live_wrappers"]).live_wrappers().items())
      else "residual wrappers (events owned by destroyed queue are freed)")
print("result:", float(out))
