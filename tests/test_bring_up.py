"""Bring-up contracts (PR 24): nothing on the chip path falls back in
silence, the compile cache is placed from outside, children stay off the
chip, and the prefill VMEM plan counts what the TPU compiler counts."""

import os
import types

import jax
import numpy as np
import pytest

from flexflow_tpu.ops.pallas.attention import (
    _VMEM_SCOPED_LIMIT,
    _prefill_plan,
    _prefill_vmem_bytes,
)
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.search.machine_model import MachineModel
from flexflow_tpu.utils import platform


# ---- compile cache placed from outside -----------------------------------
@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them (the
    suite must not arm the persistent cache — see conftest.py)."""
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    return seen


def test_compile_cache_dir_from_env_sets_no_directory(monkeypatch,
                                                      config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert platform.enable_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in config_updates


def test_compile_cache_dir_defaults_to_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expect = os.path.join(root, ".jax_cache")
    assert platform.enable_compile_cache() == expect
    assert config_updates["jax_compilation_cache_dir"] == expect


def test_with_stack_room_calls_through_from_a_frame_of_its_own_chunk():
    """``with_stack_room(fn, ...)`` is ``fn(...)`` from a frame larger than
    a 16 KiB chunk of CPython's frame stack, so that nothing called from it
    crosses a chunk boundary (platform.py says what that costs: the v5e
    host lowered one program in 0.3 s or 8 s by it)."""
    room = platform.with_stack_room
    assert room(lambda a, b=0: (a, b), 1, b=2) == (1, 2)
    with pytest.raises(ZeroDivisionError):
        room(lambda: 1 / 0)
    assert room.__code__.co_nlocals * 8 >= 128 * 1024

    # and what it calls runs in that frame's chunk: a deep recursion from
    # it returns as from anywhere else
    def down(d):
        return 0 if d == 0 else 1 + down(d - 1)

    assert room(down, 500) == 500


def test_cpu_child_env_pins_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    env = platform.cpu_child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == "--xla_foo=1"  # the rest is inherited
    assert os.environ["JAX_PLATFORMS"] == "tpu"  # the parent's is untouched


# ---- unknown hardware is an error ----------------------------------------
def _fake_mesh(kind):
    dev = types.SimpleNamespace(device_kind=kind, platform="tpu")
    return types.SimpleNamespace(devices=np.array([dev], dtype=object),
                                 size=1)


@pytest.mark.parametrize("kind,spec", [("TPU v5 lite", "v5e"),
                                       ("TPU v5", "v5p"), ("cpu", "cpu")])
def test_machine_model_keyed_by_device_kind(kind, spec):
    assert MachineModel.for_mesh(_fake_mesh(kind)).spec.name == spec


def test_machine_model_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="TPU v9"):
        MachineModel.for_mesh(_fake_mesh("TPU v9"))


def test_broken_cost_cache_raises(tmp_path):
    from flexflow_tpu.search.measure import CostCache

    p = tmp_path / "costs.json"
    p.write_text("{not json")
    with pytest.raises(ValueError, match="costs.json"):
        CostCache(str(p))
    assert CostCache(str(tmp_path / "absent.json")).data == {}


# ---- no hidden gather fallback on a TPU backend --------------------------
def _attention_op():
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.serve.ops import IncMultiHeadSelfAttention

    ff = FFModel(FFConfig())
    x = ff.create_tensor((8, 32))
    ff.inc_multihead_self_attention(x, 32, 4, 4, 8, name="attn")
    return next(n.op for n in ff.graph.nodes
                if isinstance(n.op, IncMultiHeadSelfAttention))


def test_unsupported_kernel_sharding_raises_on_tpu(monkeypatch, devices8):
    """dp x tp mesh: the kernel's shard_map covers head axes only.  Off the
    chip the op takes the gather oracle (None); on a TPU backend it must
    raise, naming the op and the mesh axes."""
    from jax.sharding import PartitionSpec as P

    op = _attention_op()
    mesh = make_mesh({"dp": 2, "tp": 2}, devices8[:4])
    ctx = types.SimpleNamespace(mesh=mesh)
    args = (ctx, ("tp",), [P(None, "tp")], P(None, "tp"), "decode attention")
    assert op._head_shard_map(*args) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"decode attention.*'dp': 2"):
        op._head_shard_map(*args)
    # a mesh the kernel CAN express still wraps, on either backend
    tp_only = types.SimpleNamespace(
        mesh=make_mesh({"tp": 2}, devices8[:2]))
    assert callable(op._head_shard_map(tp_only, *args[1:]))


# ---- the prefill VMEM plan -----------------------------------------------
def test_prefill_plan_at_the_7b_shape():
    """tile 128, KV=32, gq=1, D=128, bf16: all 32 heads per grid step need
    17.41 MB by the TPU compiler's own count (> its 16 MiB scoped limit);
    the plan halves the head chunk and keeps 256-position blocks."""
    assert _prefill_plan(32, 128, 2, 2, False, 128, 512, 2048) == (16, 256)
    assert _prefill_plan(32, 128, 2, 2, False, 128, 512, 4096) == (16, 256)
    # tile 64 and the tp=4 local shape keep every head in one grid step
    assert _prefill_plan(32, 128, 2, 2, False, 64, 512, 2048) == (32, 128)
    assert _prefill_plan(8, 128, 2, 2, False, 128, 512, 2048) == (8, 512)


# (kv_chunk, m_rows, block_s, d, kv_itemsize, kv_quant) -> MiB of scoped
# VMEM the TPU compiler says the kernel uses (``used_scoped_memory_configs``
# of the compiled call; described v5e, libtpu 0.0.34; read again at PR 60,
# whose body keeps no float32 copies of K and V — the kernel before it read
# 17.41, 11.54, 10.39, 15.46, 9.76, 10.16, 21.98 and 43.4)
_COMPILER_TOTALS_MB = [
    ((32, 128, 128, 128, 2, False), 14.34),
    ((16, 128, 256, 128, 2, False), 9.31),
    ((32, 64, 128, 128, 2, False), 8.93),
    ((32, 128, 128, 128, 1, True), 12.0),
    ((16, 128, 256, 128, 1, True), 7.55),
    ((1, 2048, 512, 128, 2, False), 9.93),
    ((8, 512, 512, 128, 2, False), 19.45),
    ((1, 9088, 512, 64, 2, False), 39.57),
    ((1, 2816, 256, 128, 2, False), 6.74),   # starcoder's MQA tile
]


@pytest.mark.parametrize("shape,measured_mb", _COMPILER_TOTALS_MB)
def test_prefill_vmem_estimate_never_below_the_compiler(shape, measured_mb):
    kc, m_rows, bs, d, kv_item, quant = shape
    est = _prefill_vmem_bytes(kc, m_rows, bs, d, 2, kv_item, quant)
    assert est >= measured_mb * 2**20 * 0.999
    # ...and is not so generous that it would refuse what the compiler takes
    assert est <= 2.0 * measured_mb * 2**20


def test_prefill_plan_without_admissible_plan_names_the_shape():
    with pytest.raises(ValueError, match=r"KV=1 D=64 m_rows=9088"):
        _prefill_plan(1, 64, 2, 2, False, 128 * 71, 512, 2048)
    assert _prefill_vmem_bytes(1, 9088, 128, 64, 2, 2, False) \
        > _VMEM_SCOPED_LIMIT
