"""chip_smoke.py rehearsed on the CPU (on-chip-measurement §2 steps 1-2).

The phases of ``chip_smoke.py`` are functions of a model config: here the
train, serve and kernel phases run end to end at tiny sizes with the REAL
Pallas kernels through the interpreter (``PT_PALLAS_INTERPRET=1``), and
the sharded phase on the 8-device virtual
mesh. ``main()`` itself has no size option and refuses to run without a
chip — pinned here too.
"""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from paddle_tpu.models import GPTConfig, LlamaConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The chip's path at tiny size: the Pallas kernels themselves,
    executed by the interpreter."""
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    return "interpret"


def test_main_refuses_without_a_chip():
    """No CPU carry-on: non-zero exit, a message naming the missing chip,
    and no result line on stdout."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU chip" in r.stderr
    assert '"ok"' not in r.stdout


def test_kernels_phase_tiny(interpreted_kernels):
    chip_smoke.kernels_phase(
        interpreted_kernels, rope_shape=(1, 16, 2, 128),
        norm_shape=(16, 128),
        paged=dict(slots=2, heads=2, head_dim=16, page_len=8, blocks=4,
                   windows=(1, 3)),
        moe=dict(batch=1, seq=16, hidden=32, experts=4, inter=64, top_k=2))


def test_train_phase_tiny(interpreted_kernels):
    cfg = LlamaConfig.tiny(num_hidden_layers=2, dtype="float32")
    out = chip_smoke.train_phase(cfg, seq=16, batches=(2,), steps=4,
                                 expect_impl=interpreted_kernels)
    assert out["batch"] == 2 and len(out["losses"]) == 5


def test_serve_phase_tiny(interpreted_kernels):
    cfg = GPTConfig.tiny(num_hidden_layers=2, hidden_size=64,
                         num_attention_heads=4, vocab_size=128)
    out = chip_smoke.serve_phase(
        cfg, prompt_lens=(3, 9, 14), max_new=4,
        gen_config=dict(max_slots=2, max_seq_len=32, page_len=8,
                        prefill_buckets=(8, 16)),
        expect_impl=interpreted_kernels)
    assert out["ttft_s"] > 0 and out["inter_token_s"] > 0


def test_sharded_phase_tiny_on_virtual_mesh(interpreted_kernels):
    """§2 step 2: the multi-chip phase's meshes and sharding rules on the
    virtual devices (8 here; the chip run uses the host's four) — with the
    kernels interpreted, so they run where the chip runs them:
    inside ``run_kernel_on_mesh``'s shard_map (GSPMD cannot partition a
    Mosaic call)."""
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    cfg = LlamaConfig.tiny(num_hidden_layers=2, dtype="float32")
    out = chip_smoke.sharded_phase(
        cfg, seq=16, batch=8, steps=3,
        meshes=({"sharding": 2, "mp": 2, "dp": 2, "level": "p_g_os"},
                {"dp": 4, "mp": 2}), expect_impl=interpreted_kernels)
    assert len(out["meshes"]) == 2
