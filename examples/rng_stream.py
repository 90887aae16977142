"""Massive pseudo-random number generator — the paper's example app
(Listing S2, rng_ccl.c) ported to the repro framework.

Structure identical to the paper (Fig. 2): the main thread drives the
``init``/``rng`` kernels on the Main queue; a communications thread reads
finished batches on the Comms queue and streams raw 64-bit values to
stdout; device-side double buffering lets generation of batch t+1 overlap
the read of batch t.  Profiling (including RNG↔READ overlap detection, the
cf4ocl headline) wraps the whole run.

Run:  PYTHONPATH=src python examples/rng_stream.py 262144 32 > /dev/null
      (n = 64-bit values per iteration, i = iterations)
Pipe into a consumer exactly like the paper:
      PYTHONPATH=src python examples/rng_stream.py 16777216 100 | consumer
"""

import sys
import threading

from repro.core import Context, DispatchQueue, ErrBox, memcheck, swap
from repro.core.compile_cache import use_compile_cache
from repro.kernels.xorshift_prng import ops as prng
from repro.prof import Prof, export_table, queue_chart

NUMRN_DEFAULT = 1 << 18
NUMITER_DEFAULT = 16


def main() -> int:
    numrn = int(sys.argv[1]) if len(sys.argv) >= 2 else NUMRN_DEFAULT
    numiter = int(sys.argv[2]) if len(sys.argv) >= 3 else NUMITER_DEFAULT

    use_compile_cache()
    err = ErrBox()
    ctx = Context.new_accel(err=err)
    err.check()
    print(f" * Device name            : {ctx.device(0).name}", file=sys.stderr)
    print(f" * Numbers per iteration  : {numrn}", file=sys.stderr)
    print(f" * Number of iterations   : {numiter}", file=sys.stderr)

    cq_main = DispatchQueue(ctx, "Main", profiling=True)
    cq_comms = DispatchQueue(ctx, "Comms", profiling=True)

    # Semaphores, exactly as in the paper's two-thread scheme (cp_sem.h)
    sem_rng = threading.Semaphore(1)
    sem_comm = threading.Semaphore(1)

    shared = {"buf_read": None, "err": None}

    def rng_out():
        """Comms thread: read finished batch, write raw bytes to stdout."""
        for _ in range(numiter):
            sem_rng.acquire()
            try:
                state = shared["buf_read"]
                host = cq_comms.enqueue_read(_BufView(state), blocking=True,
                                             name="READ_BUFFER")
            except Exception as e:  # noqa: BLE001
                shared["err"] = e
                sem_comm.release()
                return
            sem_comm.release()
            sys.stdout.buffer.write(host.tobytes()[: numrn * 8])
        sys.stdout.flush()

    class _BufView:
        """Adapter presenting a PrngState as a readable Buffer."""

        def __init__(self, state):
            import jax.numpy as jnp
            self.array = jnp.stack([state.hi, state.lo], -1)

    prof = Prof()
    prof.start()

    # init kernel: first batch of numbers = the seeds (paper §5)
    bufdev1 = cq_main.enqueue(prng.prng_init, numrn, name="INIT_KERNEL")
    cq_main.finish(err=err)
    err.check()
    bufdev2 = bufdev1

    shared["buf_read"] = bufdev1
    comms = threading.Thread(target=rng_out)
    comms.start()

    for _ in range(numiter - 1):
        sem_comm.acquire()
        if shared["err"] is not None:
            raise shared["err"]
        # rng kernel writes the NEXT batch while comms reads the current one
        bufdev2 = cq_main.enqueue(prng.prng_step, bufdev1, name="RNG_KERNEL")
        cq_main.finish(err=err)
        err.check()
        shared["buf_read"] = bufdev2
        sem_rng.release()
        bufdev1, bufdev2 = swap(bufdev1, bufdev2)
        bufdev1 = shared["buf_read"]

    comms.join()
    prof.stop()

    prof.add_queue("Main", cq_main)
    prof.add_queue("Comms", cq_comms)
    prof.calc(err=err)
    err.check()
    print(prof.get_summary(), file=sys.stderr)
    print(queue_chart(prof, width=80), file=sys.stderr)
    export_table(prof, "/tmp/rng_stream_profile.tsv")
    print(" * profile table exported to /tmp/rng_stream_profile.tsv "
          "(view with python -m repro.cli.plot_events)", file=sys.stderr)

    cq_main.destroy()
    cq_comms.destroy()
    ctx.destroy()
    return 0


if __name__ == "__main__":
    sys.exit(main())
