"""chip_smoke.py off the chip: it must refuse to run, and its phases must
keep working against the serve API (tiny widths, interpret-mode kernels) so
the script does not rot between chip runs."""

import jax
import pytest

import chip_smoke


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    out = capsys.readouterr()
    assert '"ok"' not in out.out  # no result line
    assert "needs a TPU" in out.err


@pytest.fixture
def tiny(monkeypatch):
    """opt-shaped toy widths and capacities; the kernel-presence check is a
    chip-only assertion (interpret mode lowers no tpu_custom_call)."""
    monkeypatch.setattr(chip_smoke, "OPT_6_7B", dict(
        chip_smoke.OPT_6_7B, hidden_size=64, ffn_dim=128,
        num_attention_heads=4, vocab_size=256, max_position_embeddings=256,
        word_embed_proj_dim=64))
    monkeypatch.setattr(chip_smoke, "PROMPT_LENS", (5, 20, 33))
    monkeypatch.setattr(chip_smoke, "NEW_TOKENS", 4)
    monkeypatch.setattr(chip_smoke, "SERVE", dict(
        max_requests=4, max_tokens_per_batch=16, max_seq_len=64))
    monkeypatch.setattr(chip_smoke, "COMPARE", dict(
        max_requests=2, max_tokens_per_batch=16, max_seq_len=64))
    monkeypatch.setattr(chip_smoke, "COMPARE_PROMPTS", (40, 7))
    monkeypatch.setattr(chip_smoke, "assert_kernels", lambda im: None)


def test_serve_phase_runs_at_tiny_size(tiny):
    llm, rep = chip_smoke.serve_phase(2, 1, jax.devices()[:1])
    assert rep["tokens_generated"] == 3 * 4
    assert rep["prompt_tokens"] == 58
    chip_smoke.release(llm.im)
    assert llm.im.params is None and llm.im.state is None


def test_kernel_vs_gather_compare_at_tiny_size(tiny):
    d_lmax, d_topk = chip_smoke.compare_phase(
        2, jax.devices(), 1, True, 1, False, "kernel-vs-gather",
        chip_smoke.KERNEL_TOL_ULPS)
    assert d_lmax >= 0.0 and d_topk >= 0.0


def test_compare_outputs_rejects_a_wrong_answer():
    import numpy as np

    a = [(np.ones(4, np.float32), np.zeros((4, 2), np.float32),
          np.zeros((4, 2), np.int32))]
    off = [(a[0][0] + 0.5, a[0][1], a[0][2])]
    chip_smoke.compare_outputs(a, a, "same", 8)
    with pytest.raises(AssertionError, match="logits_max differ"):
        chip_smoke.compare_outputs(a, off, "off", 8)
    bad = [(a[0][0] * np.nan, a[0][1], a[0][2])]
    with pytest.raises(AssertionError, match="non-finite"):
        chip_smoke.compare_outputs(a, bad, "nan", 8)
