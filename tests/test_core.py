"""Unit tests for the cf4ocl wrapper layer (repro.core)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as c
from repro.core.errors import Code, ErrBox, ReproError, err_string


class TestErrors:
    def test_err_string_known(self):
        assert "Success" in err_string(0)
        assert "build" in err_string(Code.BUILD_PROGRAM_FAILURE).lower()

    def test_err_string_unknown(self):
        assert "Unknown" in err_string(-31337)

    def test_every_code_has_err_string(self):
        """Single conversion function, total over the enum: every Code
        member — including additions — must map to a human-readable
        string (a KeyError here means a code was added without one)."""
        for code in Code:
            s = err_string(code)
            assert s and "Unknown" not in s, code
        # the fault-tolerance additions specifically
        assert "NaN" in err_string(Code.NUMERIC_FAULT)
        assert "deadline" in err_string(Code.DEADLINE_EXCEEDED).lower()
        assert "cancel" in err_string(Code.CANCELLED).lower()
        assert "retries" in err_string(Code.SUBMISSION_FAILURE).lower()

    def test_dual_reporting_raise(self):
        with pytest.raises(ReproError):
            c.Context.new_from_filters(
                c.Filters().custom(lambda d: False))

    def test_dual_reporting_box(self):
        box = ErrBox()
        out = c.Context.new_from_filters(
            c.Filters().custom(lambda d: False), err=box)
        assert out is None and box.set
        assert box.code == Code.DEVICE_NOT_FOUND
        box.clear()
        assert not box.set


class TestWrapperLifecycle:
    def test_wrap_identity(self):
        d0 = jax.devices()[0]
        a = c.Device.wrap(d0)
        b = c.Device.wrap(d0)
        assert a is b
        a.ref()
        a.destroy()
        a.destroy()

    def test_memcheck_detects_leak(self):
        ctx = c.Context.new_accel()
        assert not c.memcheck()
        assert "Context" in c.live_wrappers()
        ctx.destroy()

    def test_info_cache(self):
        dev = c.all_devices()[0]
        calls = []
        v1 = dev.get_info("X_CUSTOM", query=lambda d: calls.append(1) or 42)
        v2 = dev.get_info("X_CUSTOM")
        assert v1 == v2 == 42 and len(calls) == 1


class TestContextQueueBuffer:
    def test_context_device_indexing(self):
        ctx = c.Context.new_accel()
        assert ctx.num_devices >= 1
        box = ErrBox()
        assert ctx.device(999, err=box) is None and box.set

    def test_queue_events_and_finish(self):
        ctx = c.Context.new_accel()
        q = c.DispatchQueue(ctx, "T")
        f = jax.jit(lambda x: x * 2)
        q.enqueue(f, jnp.ones((8,)), name="DOUBLE")
        q.finish()
        evts = q.events
        assert len(evts) == 1 and evts[0].name == "DOUBLE"
        assert evts[0].duration_ns is not None and evts[0].duration_ns >= 0

    def test_buffer_roundtrip_and_swap(self):
        ctx = c.Context.new_accel()
        b1 = c.Buffer.new(ctx, (4, 4), jnp.float32, fill=1.0)
        b2 = c.Buffer.new(ctx, (4, 4), jnp.float32, fill=2.0)
        b1, b2 = c.swap(b1, b2)
        assert float(b1.get()[0, 0]) == 2.0
        b1.put(np.full((4, 4), 7.0))
        assert float(b1.get().sum()) == 112.0
        with pytest.raises(ReproError):
            b1.put(np.zeros((3, 3)))

    def test_queue_read_write(self):
        ctx = c.Context.new_accel()
        q = c.DispatchQueue(ctx, "IO")
        b = c.Buffer.new(ctx, (16,), jnp.int32)
        q.enqueue_write(b, np.arange(16), name="H2D")
        host = q.enqueue_read(b, name="D2H")
        assert (host == np.arange(16)).all()
        assert [e.command_type for e in q.events] == \
            ["WRITE_BUFFER", "READ_BUFFER"]

    def test_transfer_loops_prune_pending_outputs(self):
        """enqueue_write/enqueue_read must prune completed submissions
        like enqueue does — a transfer-heavy loop must not pin every
        buffer it ever touched until the next finish()."""
        ctx = c.Context.new_accel()
        q = c.DispatchQueue(ctx, "IO")
        b = c.Buffer.new(ctx, (16,), jnp.int32)
        for i in range(32):
            q.enqueue_write(b, np.full(16, i), name="H2D")
            jax.block_until_ready(b.array)   # everything settled ⇒ prunable
        assert len(q._pending_outputs) <= 1
        q.finish()

    def test_is_ready_keeps_failures_pending(self):
        """An output whose is_ready() raises a non-deletion error must
        stay pending (so finish() surfaces the failure); deleted/donated
        buffers count as finished."""
        from repro.core.queue import _is_ready

        class Boom:
            def is_ready(self):
                raise RuntimeError("INTERNAL: async computation failed")

        class Deleted:
            def is_ready(self):
                raise RuntimeError("Array has been deleted")

        class Ready:
            def is_ready(self):
                return True

        assert _is_ready(Ready())
        assert _is_ready(Deleted())          # donated ⇒ prunable
        assert not _is_ready(Boom())         # failure ⇒ keep for finish()
        assert not _is_ready([Ready(), Boom()])


class TestProgramKernel:
    def test_build_lower_compile_analyze(self):
        ctx = c.Context.new_accel()
        prog = c.Program(ctx, lambda x: (x @ x).sum())
        prog.build()
        prog.lower(jax.ShapeDtypeStruct((128, 128), jnp.float32))
        prog.compile()
        an = prog.analyze()
        assert an.flops > 2 * 128**3 * 0.9
        assert an.collectives.total_bytes == 0
        k = prog.get_kernel()
        out = k(jnp.eye(128, dtype=jnp.float32))  # x64-safe: matches the lowered f32 signature
        assert float(out) == 128.0

    def test_build_log_on_failure(self):
        ctx = c.Context.new_accel()
        prog = c.Program(ctx, lambda x: x @ jnp.ones((3, 3)))
        prog.build()
        with pytest.raises(ReproError) as ei:
            prog.lower(jax.ShapeDtypeStruct((4, 4), jnp.float32))
        assert ei.value.code in (Code.BUILD_PROGRAM_FAILURE,
                                 Code.COMPILE_FAILURE)
        assert prog.build_log

    def test_suggest_batching_alignment(self):
        dev = c.all_devices()[0]
        gws, lws = c.suggest_batching(100_000, dev)
        quantum = dev.target_spec.vpu_sublanes * dev.target_spec.vpu_lanes
        assert gws % lws == 0 and lws % quantum == 0 and gws >= 100_000

    def test_suggest_matmul_tiles_vmem(self):
        dev = c.all_devices()[0]
        bm, bn, bk = c.suggest_matmul_tiles(4096, 4096, 4096, dev)
        spec = dev.target_spec
        ws = 2 * (bm * bk + bk * bn + bm * bn)
        assert ws <= spec.vmem_bytes // 2
        assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0


class TestHloAnalysis:
    def test_shape_bytes(self):
        from repro.core.hlo_analysis import shape_bytes
        assert shape_bytes("bf16[128,256]{1,0}") == 128 * 256 * 2
        assert shape_bytes("(f32[8]{0}, s8[4]{0})") == 36

    def test_collective_parse(self):
        from repro.core.hlo_analysis import collective_stats
        txt = """
  %ag = bf16[64,128]{1,0} all-gather(%p), replica_groups={{0,1,2,3}}
  %ar = f32[128]{0} all-reduce(%q), replica_groups=[2,256]<=[512]
"""
        st = collective_stats(txt)
        assert st.counts == {"all-gather": 1, "all-reduce": 1}
        ag = 64 * 128 * 2 * 3 // 4
        ar = 2 * 128 * 4 * 255 // 256
        assert st.bytes_by_kind["all-gather"] == ag
        assert st.bytes_by_kind["all-reduce"] == ar


class TestChipSpecs:
    def test_known_kind(self):
        spec = c.hw.spec_for("TPU v5 lite")
        assert spec is c.hw.TPU_V5E
        assert spec.peak_bf16_flops == 197e12 and spec.hbm_bandwidth == 819e9
        assert "TPU v5e" in spec.source

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
    def test_unknown_kind_raises(self, kind):
        with pytest.raises(ReproError) as ei:
            c.hw.spec_for(kind)
        assert ei.value.code is Code.DEVICE_NOT_FOUND

    def test_min_hbm_needs_a_known_kind(self):
        box = ErrBox()
        devs = c.Filters().min_hbm(1).select(err=box)
        assert all(d.kind in c.hw.SPECS for d in devs)
        assert box.set == (not devs)


class TestDeviceSelection:
    def test_new_accel_takes_the_started_backend(self):
        ctx = c.Context.new_accel()
        assert ctx.num_devices == len(jax.devices())
        assert {d.platform for d in ctx.devices} == {jax.default_backend()}


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_var_wins(self, monkeypatch, tmp_path, restore_cache_dir):
        from repro.core.compile_cache import use_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before   # untouched

    def test_default_is_the_checkout(self, monkeypatch, restore_cache_dir):
        import pathlib
        from repro.core.compile_cache import use_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = pathlib.Path(__file__).resolve().parents[1]
        want = str(root / ".jax_cache")
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
