"""Compile the kernels ``chip_smoke.py`` runs, at its sizes, for a described
TPU v5e — no chip needed.

Interpret mode cannot see what Mosaic refuses (unaligned blocks, rank-1
blocks, VMEM overuse, ops with no TPU lowering); the TPU compiler that is
installed with JAX can, for a topology it is only told about.  Each test
lowers one kernel (or one serve step) with ``ShapeDtypeStruct`` arguments
placed on a described chip and compiles it.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every xdist worker imports
this file.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: the 7-point stencil on 128^3 rows (chip_smoke.py's mod2as and CG)
N_STENCIL = 128 ** 3
OFFSETS_7PT = (-128 * 128, -128, -1, 0, 1, 128, 128 * 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture
def tpu_plane(monkeypatch):
    """Registry selection as it runs on the chip: code that asks the
    platform sees 'tpu' (the described chip is not attached, so JAX itself
    still reports the CPU), and the Pallas plane is requested, whatever
    ``REPRO_KERNELS`` says."""
    from repro.core import registry

    real = registry.select_context

    def on_tpu():
        return dataclasses.replace(real(), platform="tpu")

    monkeypatch.setattr(registry, "select_context", on_tpu)
    with registry.use_backend("pallas"):
        yield


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _called_from(hlo, name):
    """The text of HLO computation ``name`` and of every computation it
    calls, transitively."""
    comps = dict(re.findall(r"^(?:ENTRY )?%([\w.-]+) .*?\{\n(.*?)^\}",
                            hlo, re.M | re.S))
    seen, todo = set(), [name]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.-]+)",
                               comps[c])
    return "\n".join(comps[c] for c in seen)


def _reductions_over(hlo, n):
    """The names of the ``reduce`` instructions in ``hlo`` whose operand
    holds ``n`` elements."""
    shapes = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo))
    return [name for name, operand in
            re.findall(r"%([\w.-]+) = \S+ reduce\(%([\w.-]+)", hlo)
            if math.prod(int(d) for d in shapes[operand].split(",") if d)
            == n]


def _cg_dia_body(one_chip):
    """The optimised HLO of ``cg_solve``'s while body, and of every
    computation it calls, on the 7-point DIA operator under O2."""
    from repro.core import ExecLevel, unwrap, use_level
    from repro.numerics.solvers import cg_solve
    from repro.numerics.sparse import DIA

    def solve(diags, b):
        a = DIA(diags=diags, offsets=OFFSETS_7PT,
                shape=(N_STENCIL, N_STENCIL))
        return unwrap(cg_solve(a, b, max_iters=10).x)

    with use_level(ExecLevel.O2):
        hlo = _compile(solve, _sds(one_chip, (len(OFFSETS_7PT), N_STENCIL),
                                   jnp.float32),
                       _sds(one_chip, (N_STENCIL,), jnp.float32)).as_text()
    return _called_from(hlo, re.search(r"while\(.*\bbody=%([\w.-]+)",
                                       hlo).group(1))


@pytest.mark.usefixtures("no_persistent_cache")
class TestSmokeKernelsCompileForV5e:
    @pytest.mark.parametrize("n,dtype", [(8192, jnp.bfloat16),
                                         (4096, jnp.float32)])
    def test_matmul(self, one_chip, n, dtype):
        from repro.kernels import matmul as mm_k

        a = _sds(one_chip, (n, n), dtype)
        _compile(lambda x, y: mm_k.matmul(x, y), a, a)

    @pytest.mark.parametrize("x_vmem_bytes", [16 << 20, 0],
                             ids=["x_in_vmem", "x_in_hbm_windows"])
    def test_spmv_ell(self, one_chip, x_vmem_bytes):
        from repro.kernels import spmv as spmv_k

        w = len(OFFSETS_7PT)
        _compile(lambda v, c, x: spmv_k.spmv_ell(
            v, c, x, x_vmem_bytes=x_vmem_bytes),
            _sds(one_chip, (N_STENCIL, w), jnp.float32),
            _sds(one_chip, (N_STENCIL, w), jnp.int32),
            _sds(one_chip, (N_STENCIL,), jnp.float32))

    def test_spmv_dia(self, one_chip):
        """x reaches the kernel unpadded, as one (1, n) operand: no pad is
        written on the way in."""
        from repro.kernels import spmv as spmv_k

        hlo = _compile(lambda d, x: spmv_k.spmv_dia(d, OFFSETS_7PT, x),
                       _sds(one_chip, (len(OFFSETS_7PT), N_STENCIL),
                            jnp.float32),
                       _sds(one_chip, (N_STENCIL,), jnp.float32)).as_text()
        assert "pad(" not in hlo
        operands, = re.findall(r"operand_layout_constraints=\{(.*?\})\}",
                               hlo)
        assert re.findall(r"\w+\[[\d,]*\]", operands) == [
            f"f32[{len(OFFSETS_7PT)},{N_STENCIL}]", f"f32[1,{N_STENCIL}]"]

    def test_spmv_dia_7pt_256_keeps_operand_shapes(self, one_chip):
        """The benchmark's 7-point operator at 256^3 rows: a multiple of
        1024, so x goes in as its (1, n) view, and nothing is padded."""
        from repro.kernels import spmv as spmv_k

        n, offs = 256 ** 3, (-256 * 256, -256, -1, 0, 1, 256, 256 * 256)
        hlo = _compile(lambda d, x: spmv_k.spmv_dia(d, offs, x),
                       _sds(one_chip, (7, n), jnp.float32),
                       _sds(one_chip, (n,), jnp.float32)).as_text()
        assert "pad(" not in hlo
        operands, = re.findall(r"operand_layout_constraints=\{(.*?\})\}",
                               hlo)
        assert re.findall(r"\w+\[[\d,]*\]", operands) == [
            f"f32[7,{n}]", f"f32[1,{n}]"]

    def test_spmv_dia_27pt_shard_with_halo(self, one_chip):
        """One shard of HPCG's 27-point operator at 360^3 rows a chip, with
        the max|offset| = 129,961 rows of each neighbour: its blocks fit
        VMEM, the ragged last tile copies neither the diagonals nor x
        (46,656,000 is no multiple of 1024, so x and y go as the vector)."""
        from repro.kernels import spmv as spmv_k

        nx, n = 360, 360 ** 3
        offs = tuple(sorted({dx + nx * (dy + nx * dz) for dz in (-1, 0, 1)
                             for dy in (-1, 0, 1) for dx in (-1, 0, 1)}))
        m = max(abs(o) for o in offs)
        hlo = _compile(
            lambda d, x, lo, hi: spmv_k.spmv_dia(d, offs, x, halo=(lo, hi)),
            _sds(one_chip, (27, n), jnp.float32),
            _sds(one_chip, (n,), jnp.float32),
            _sds(one_chip, (m,), jnp.float32),
            _sds(one_chip, (m,), jnp.float32)).as_text()
        assert "pad(" not in hlo
        operands, = re.findall(r"operand_layout_constraints=\{(.*?\})\}",
                               hlo)
        assert re.findall(r"\w+\[[\d,]*\]", operands) == [
            f"f32[27,{n}]", f"f32[{n}]", "f32[3,130048]"]

    @pytest.mark.usefixtures("tpu_plane")
    def test_cg_dia_loop_writes_no_pad(self, one_chip):
        """``cg_solve`` on a DIA operator under the Pallas plane: its
        while body (and every computation it calls) holds the kernel and
        no pad of p."""
        body = _cg_dia_body(one_chip)
        assert "tpu_custom_call" in body
        assert "pad(" not in body

    @pytest.mark.usefixtures("tpu_plane")
    def test_cg_dia_loop_reduces_only_r_r(self, one_chip):
        """The kernel returns p.Ap with Ap: outside it the while body
        reduces one length-n vector, r.r, and no pass reads p and Ap back
        for p.Ap; nor does a pass scale Ap in the kernel's (1, n) layout
        on its own (XLA moves the reshape of a lone output past alpha *
        Ap unless held)."""
        body = _cg_dia_body(one_chip)
        (r_r,) = _reductions_over(body, N_STENCIL)
        assert not re.search(rf"= f32\[1,{N_STENCIL}\]\S* fusion\(", body)
        kernel, = re.findall(r"= (.*?) custom-call\(", body)
        assert kernel.startswith(f"(f32[1,{N_STENCIL}]"), kernel
        assert "f32[1,1]" in kernel, kernel

    @pytest.mark.usefixtures("tpu_plane")
    def test_spmv_dia_op_takes_no_dot(self, one_chip):
        """``ops.spmv_dia`` at the SpMV cell's shapes (256^3 rows) builds
        the launch without the dot: one output, no reduction."""
        from repro.kernels import ops

        n, offs = 256 ** 3, (-256 * 256, -256, -1, 0, 1, 256, 256 * 256)
        hlo = _compile(lambda d, x: ops.spmv_dia(d, offs, x),
                       _sds(one_chip, (7, n), jnp.float32),
                       _sds(one_chip, (n,), jnp.float32)).as_text()
        assert "reduce(" not in hlo
        kernel, = re.findall(r"= (.*?) custom-call\(", hlo)
        assert kernel.startswith(f"f32[1,{n}]"), kernel

    def test_fft_stage(self, one_chip):
        from repro.kernels import fft as fft_k

        pair = _sds(one_chip, (2, (1 << 22) // 2), jnp.float32)
        tw = _sds(one_chip, ((1 << 22) // 2,), jnp.float32)
        _compile(fft_k.fft_stage, pair, pair, tw, tw)

    @pytest.mark.parametrize("b,lq,lk", [(1, 128, 128), (2, 512, 512)],
                             ids=["prefill_chunk", "forward"])
    def test_flash_causal_state(self, one_chip, b, lq, lk):
        from repro.kernels import flash_attention as fa_k

        _compile(lambda q, k, v: fa_k.flash_attention(
            q, k, v, causal=True, return_state=True),
            _sds(one_chip, (b, 16, lq, 128), jnp.bfloat16),
            _sds(one_chip, (b, 8, lk, 128), jnp.bfloat16),
            _sds(one_chip, (b, 8, lk, 128), jnp.bfloat16))

    @pytest.mark.parametrize("b,lq", [(8, 1), (1, 128)],
                             ids=["paged_decode", "chunk_prefix"])
    def test_flash_kv_len(self, one_chip, b, lq):
        from repro.kernels import flash_attention as fa_k

        _compile(lambda q, k, v, n: fa_k.flash_attention(
            q, k, v, causal=False, return_state=True, kv_len=n),
            _sds(one_chip, (b, 16, lq, 128), jnp.bfloat16),
            _sds(one_chip, (b, 8, 640, 128), jnp.bfloat16),
            _sds(one_chip, (b, 8, 640, 128), jnp.bfloat16),
            _sds(one_chip, (b,), jnp.int32))

    @pytest.mark.usefixtures("tpu_plane")
    def test_qwen3_serve_decode_step(self, one_chip):
        """One ContinuousEngine decode step of qwen3-1.7b at published
        widths (8 slots, 640-token slots, page 64), from eval_shape
        shapes: the Pallas paged-attention path inside the full model."""
        from repro.configs import get_config
        from repro.models.lm import LM
        from repro.serve.kvcache import init_cache_state, make_spec

        cfg = get_config("qwen3-1.7b")
        lm = LM(cfg)
        spec = make_spec(cfg, num_slots=8, max_tokens=640)

        def place(tree):
            return jax.tree_util.tree_map(
                lambda s: _sds(one_chip, s.shape, s.dtype), tree)

        params = place(jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
        state = place(jax.eval_shape(lambda: init_cache_state(cfg, spec)))
        tokens = _sds(one_chip, (8, 1), jnp.int32)
        active = _sds(one_chip, (8,), jnp.int32)
        compiled = _compile(lm.decode_step_paged, params, state, tokens,
                            active)
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 16e9
