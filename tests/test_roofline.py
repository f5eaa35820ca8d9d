"""Roofline tooling: jaxpr flop/byte counter correctness on known
workloads; HLO collective parser on synthetic and real HLO text; the
fused-kernel 3-read/2-write cost-model pin (the autotune loop's contract
-- if the kernel body or the walker drifts from the hand model, the
block-size tuning silently optimizes the wrong target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (HW, collective_bytes, model_flops)
from repro.roofline.jaxpr_cost import Cost, trace_cost
from repro.roofline.kernel_model import (fused_update_cost, gpu_padded_shape,
                                         predicted_intensity, round_cost)


class TestJaxprCounter:
    def test_plain_matmul(self):
        m, k, n = 64, 128, 256
        a = jax.ShapeDtypeStruct((m, k), jnp.float32)
        b = jax.ShapeDtypeStruct((k, n), jnp.float32)
        c = trace_cost(lambda a, b: a @ b, a, b)
        assert c.flops == 2 * m * k * n
        assert c.bytes == 4 * (m * k + k * n + m * n)

    def test_batched_einsum(self):
        x = jax.ShapeDtypeStruct((8, 16, 32), jnp.float32)
        w = jax.ShapeDtypeStruct((8, 32, 64), jnp.float32)
        c = trace_cost(lambda x, w: jnp.einsum("bik,bkj->bij", x, w), x, w)
        assert c.flops == 2 * 8 * 16 * 32 * 64

    def test_scan_multiplies_by_length(self):
        m, k, L = 64, 128, 7
        def f(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            return jax.lax.scan(body, x, ws)[0]
        ws = jax.ShapeDtypeStruct((L, k, k), jnp.float32)
        x = jax.ShapeDtypeStruct((m, k), jnp.float32)
        c = trace_cost(f, ws, x)
        dot = 2 * m * k * k
        assert abs(c.flops - L * (dot + m * k)) / (L * dot) < 0.02

    def test_train_step_counts_fwd_bwd_remat(self):
        """fwd + remat-fwd + dW + dh = 4 dots per layer."""
        m, k, L = 64, 128, 4
        def loss(ws, x):
            def body(h, w):
                return jax.checkpoint(lambda h, w: jnp.tanh(h @ w))(h, w), None
            return jnp.sum(jax.lax.scan(body, x, ws)[0] ** 2)
        def step(ws, x):
            _, g = jax.value_and_grad(loss)(ws, x)
            return jax.tree.map(lambda a, b: a - b, ws, g)
        ws = jax.ShapeDtypeStruct((L, k, k), jnp.float32)
        x = jax.ShapeDtypeStruct((m, k), jnp.float32)
        c = trace_cost(step, ws, x)
        expected = L * 4 * 2 * m * k * k
        assert abs(c.flops - expected) / expected < 0.05

    def test_while_trips_hint(self):
        def f(x):
            def cond(c):
                return c[1] < 10
            def body(c):
                x, i = c
                return (jnp.tanh(x @ x), i + 1)
            return jax.lax.while_loop(cond, body, (x, 0))[0]
        x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        c1 = trace_cost(f, x, while_trips=1.0)
        c10 = trace_cost(f, x, while_trips=10.0)
        assert abs(c10.flops / c1.flops - 10.0) < 0.1


def _kernel_operands(e, s, dtype=jnp.float32):
    return (jax.ShapeDtypeStruct((e, s, s), dtype),
            jax.ShapeDtypeStruct((e, s), dtype),
            jax.ShapeDtypeStruct((e, s), dtype),
            jax.ShapeDtypeStruct((e, s), jnp.bool_))


class TestKernelCostModel:
    """The roofline prediction pin: jaxpr-walk cost of one fused update
    must match the hand-counted 3-read/2-write model. Shapes are chosen
    pre-aligned (power-of-two states, block-multiple edges) so the model
    and the launch agree exactly on bytes; flops get tolerance for the
    O(S) tail, which depends on how jax traces broadcasts."""

    @pytest.mark.parametrize("s,e,dtype", [(2, 1024, jnp.float32),
                                           (4, 1024, jnp.float32),
                                           (8, 512, jnp.float32),
                                           (4, 1024, jnp.bfloat16)])
    @pytest.mark.parametrize("semiring", ["sum", "max"])
    def test_gpu_kernel_matches_model(self, s, e, dtype, semiring):
        from repro.kernels.triton_update import fused_update_e
        db = jnp.dtype(dtype).itemsize
        e_pad, s_pad, _ = gpu_padded_shape(e, s, db)
        assert (e_pad, s_pad) == (e, s)   # pre-aligned by construction
        c = trace_cost(lambda *o: fused_update_e(
            *o, semiring=semiring, interpret=True), *_kernel_operands(e, s, dtype))
        model = fused_update_cost(e, s, dtype_bytes=db, semiring=semiring)
        assert c.bytes == model.bytes     # 3 reads + 2 writes + mask, exact
        assert abs(c.flops - model.flops) / model.flops < 0.25

    def test_tpu_kernel_same_traffic_contract(self):
        """The TPU-layout kernel streams the same operands (transposed), so
        the same byte model holds; flops agree with the TPU kernel's fit
        (software exp/log, two loops over source states)."""
        from repro.kernels.message_update import fused_update_t
        s, e = 4, 1024
        ops = (jax.ShapeDtypeStruct((s, s, e), jnp.float32),
               jax.ShapeDtypeStruct((s, e), jnp.float32),
               jax.ShapeDtypeStruct((s, e), jnp.float32),
               jax.ShapeDtypeStruct((s, e), jnp.bool_))
        c = trace_cost(lambda *o: fused_update_t(*o, interpret=True), *ops)
        model = fused_update_cost(e, s, kernel="tpu")
        assert c.bytes == model.bytes
        assert abs(c.flops - model.flops) / model.flops < 0.25

    def test_pallas_flops_scale_with_grid(self):
        """The pallas_call handler multiplies body flops by the grid size:
        doubling the edge count (same block) must double the count."""
        from repro.kernels.triton_update import fused_update_e
        s = 4
        f = lambda *o: fused_update_e(*o, interpret=True, blk_e=256)
        c1 = trace_cost(f, *_kernel_operands(1024, s))
        c2 = trace_cost(f, *_kernel_operands(2048, s))
        assert abs(c2.flops / c1.flops - 2.0) < 1e-6
        assert abs(c2.bytes / c1.bytes - 2.0) < 1e-6

    def test_intensity_memory_bound_and_dtype_scaling(self):
        """BP state counts sit far below the roofline ridge point, and
        halving the operand width must raise intensity (same flops, fewer
        bytes) -- the quantity the BLK_E autotune targets."""
        hw = HW()
        ridge = hw.peak_flops / hw.hbm_bw
        for s in [2, 8, 96]:
            i32 = predicted_intensity(s, dtype_bytes=4)
            i16 = predicted_intensity(s, dtype_bytes=2)
            assert 0.0 < i32 < ridge          # memory-bound everywhere
            assert i16 > i32
        assert predicted_intensity(2, semiring="max") < \
            predicted_intensity(2, semiring="sum")

    def test_round_cost_dominated_by_update(self):
        """Per-scheduler round trace: the fused update is the hot spot, so
        the round's bytes are within a small factor of the kernel's."""
        from repro.core.schedulers import get_scheduler
        from repro.kernels.ops import make_triton_update
        from repro.pgm import ising_grid
        pgm = ising_grid(8, 2.0, seed=0)
        kernel = fused_update_cost(pgm.n_edges, pgm.n_states_max,
                                   padded=True)
        for name in ["lbp", "rbp", "rnbp"]:
            c = round_cost(pgm, get_scheduler(name),
                           make_triton_update(True))
            assert c.flops >= kernel.flops and c.bytes >= kernel.bytes
            assert c.bytes < 6.0 * kernel.bytes


SYNTH_HLO = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %r = f32[] add(%a, %b)
}

%wbody (arg: (f32[128,256], s32[])) -> (f32[128,256], s32[]) {
  %ar = f32[128,256]{1,0} all-reduce(%x), to_apply=%add
  %cp = f32[64]{0} collective-permute(%y), source_target_pairs={{0,1}}
  ROOT %t = tuple(%ar, %c)
}

%wcond (arg: (f32[128,256], s32[])) -> pred[] {
  %k = s32[] constant(12)
  ROOT %cmp = pred[] compare(%i, %k), direction=LT
}

ENTRY %main (p: f32[128,256]) -> f32[128,256] {
  %ag = bf16[32,64]{1,0} all-gather(%p2), dimensions={0}
  %w = (f32[128,256], s32[]) while(%init), condition=%wcond, body=%wbody
  ROOT %out = f32[128,256] get-tuple-element(%w), index=0
}
"""


class TestCollectiveParser:
    def test_synthetic_hlo_with_while(self):
        out = collective_bytes(SYNTH_HLO)
        # all-gather once: 32*64*2 bytes
        assert out["all-gather"] == 32 * 64 * 2
        # all-reduce inside 12-trip while, x2 ring factor
        assert out["all-reduce"] == 12 * 128 * 256 * 4 * 2
        assert out["collective-permute"] == 12 * 64 * 4
        assert out["total"] == (out["all-gather"] + out["all-reduce"]
                                + out["collective-permute"])

    def test_lhs_name_not_confused_with_op(self):
        txt = """
ENTRY %main (p: f32[4]) -> f32[4] {
  %all-reduce.5 = f32[4]{0} add(%p, %p)
  ROOT %r = f32[4] copy(%all-reduce.5)
}
"""
        out = collective_bytes(txt)
        assert out["total"] == 0.0

    def test_async_start_done_counted_once(self):
        txt = """
ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %ars = (f32[8,8], f32[8,8]) all-reduce-start(%p), to_apply=%add
  ROOT %ard = f32[8,8] all-reduce-done(%ars)
}
"""
        out = collective_bytes(txt)
        assert out["all-reduce"] == 8 * 8 * 4 * 2   # once, with ring factor


class TestModelFlops:
    def test_moe_active_fraction(self):
        from repro.configs import get
        from repro.models import build_model
        cfg = get("granite_moe_3b_a800m")
        specs = build_model(cfg).param_specs()
        mf_all = model_flops(specs, 1000, cfg=None, kind="train")
        mf_active = model_flops(specs, 1000, cfg=cfg, kind="train")
        assert mf_active < mf_all          # expert scaling applied
        # experts are 40, top-8 -> expert flops scaled by 0.2
        assert mf_active > 0.1 * mf_all

    def test_serve_multiplier(self):
        from repro.configs import get
        from repro.models import build_model
        cfg = get("qwen3_4b")
        specs = build_model(cfg).param_specs()
        assert model_flops(specs, 100, cfg=cfg, kind="train") == \
            3 * model_flops(specs, 100, cfg=cfg, kind="decode")
