"""Each reference against the system at toy widths, the control that must
fail, and run.py end to end on toy cells (CPU; Pallas interpreted)."""

import importlib
import json
import os

import jax
import pytest

from conftest import toy_conf

from benchmark import control, run


def _gate(chips):
    return jax.devices()[:chips], {"flops_bf16": 197e12,
                                   "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("name", ["toy-opt", "toy-bigcode"])
def test_reference_agrees_and_the_control_fails(pallas_on_cpu, name):
    """The scheduler's calls (prefill scan of several chunks, flat prefill,
    two chained decode scans with a join between them, flat decode steps)
    against the reference's full forward pass; then the same with an int8
    KV cache and with int8 weights, which must NOT pass.  The limits are the
    toy's own (conftest.LIMITS)."""
    conf = toy_conf(name)
    hf = {k: v for k, v in conf.items() if k != "benchmark"}
    ref = importlib.import_module("benchmark.reference." + hf["model_type"])
    devices, quiet = jax.devices()[:1], (lambda m: None)
    sound = control.read_numbers(hf, conf["benchmark"], ref,
                                 [1, 2 ** 31 + 5], "", devices, log=quiet)
    assert all(ok for _, ok, _ in sound), sound
    worst_sound = max(n["logprob_rms"] for _, _, n in sound)
    for which in conf["benchmark"]["controls"]:
        broken = control.read_numbers(hf, conf["benchmark"], ref, [1, 2, 3],
                                      which, devices, log=quiet)
        assert not any(ok for _, ok, _ in broken), (which, broken)
        least_broken = min(n["logprob_rms"] for _, _, n in broken)
        assert least_broken > 1.5 * worst_sound, which


def test_a_token_the_reference_would_not_pick_is_seen(pallas_on_cpu):
    """The structural control of ``token_gap_ulps``: the programs that return
    tokens only (prefill scan, decode scan, join) are judged by their tokens.
    Tokens as the program made them pass; the same tokens given to the
    neighbouring positions (a scan that wrote or read one position off) are
    off by the logit scale."""
    from benchmark import check

    conf = toy_conf("toy-opt")
    hf = {k: v for k, v in conf.items() if k != "benchmark"}
    dep = conf["benchmark"]
    ref = importlib.import_module("benchmark.reference.opt")
    llm = run.build(hf, dep, jax.devices()[:1])
    key = run.seed_weights(llm, ref, hf, 5, dep["precision"])
    im = llm.im
    seqs = check.check_sequences(5, hf["vocab_size"], im.prefill_tile,
                                 im.max_tokens, im.max_seq_len)
    rows, gen = check.drive(im, seqs)
    assert [len(g) for g in gen] == [
        1 + 2 * check.SCAN_STEPS + check.TAIL_STEPS] * 2 + [
        1 + check.SCAN_STEPS + check.TAIL_STEPS]
    wanted = [sorted({p for s, p, *_ in rows if s == i}
                     | {len(seqs[i]) - 1 + k for k in range(len(gen[i]))})
              for i in range(3)]
    logits = check.reference_logits(
        ref, hf, key, dep["precision"],
        [p + g[:-1] for p, g in zip(seqs, gen)], wanted)
    sound, _ = check.compare(rows, gen, seqs, logits, wanted, im.topk)
    assert sound["token_gap_ulps"] <= dep["correct"]["token_gap_ulps"]
    shifted = [g[1:] + g[:1] for g in gen]
    broken, _ = check.compare(rows, shifted, seqs, logits, wanted, im.topk)
    assert broken["token_gap_ulps"] > 50


@pytest.mark.parametrize("cell,metrics", [
    ("toy-opt.toy-closed", {"total_tok_s", "setup_s"}),
    ("toy-bigcode.toy-open", {"ttft_p90_ms", "tpot_pooled_ms", "setup_s"}),
])
def test_run_py_end_to_end_on_a_toy_cell(pallas_on_cpu, toy_root, capsys,
                                         cell, metrics):
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 99),
                   "--seconds", "2", "--trace", "0"],
                  root=toy_root, data=toy_root, gate=_gate)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == metrics
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert any(line.startswith("samples:") for line in out)
    assert any("0 lowerings or compiles in it" in line for line in out)
    assert not any(line.startswith("NOT CORRECT") for line in out)


@pytest.mark.parametrize("depth,says", [
    (2, "the window never opened"),
    (8, "the queue ran dry"),
])
def test_a_closed_queue_too_shallow_ends_without_a_result(
        pallas_on_cpu, toy_root, capsys, depth, says):
    """Fewer requests than slots: the window never opens.  More, but not
    enough for the window: the loop serves them all and returns; the run
    says at what pace and how deep the queue would have had to be, on both
    streams, and prints no result line."""
    path = os.path.join(toy_root, "traffic", "toy-closed.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(queue_depth=depth, round=4)
    with open(path, "w") as f:
        json.dump(mix, f)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "toy-opt.toy-closed", "--seed", "11",
                  "--seconds", "3600", "--trace", "0"],
                 root=toy_root, data=toy_root, gate=_gate)
    assert e.value.code not in (0, None)
    streams = capsys.readouterr()
    assert '"correct"' not in streams.out
    for text in (streams.out, streams.err):
        assert says in text
    if depth > 2:
        last = streams.err.strip().splitlines()[-1]
        assert f"all {depth} requests" in last and "tokens/s" in last
        # 1.5 x the requests x (the wait for the window + 3600 s) / the
        # seconds the loop took, rounded up to whole rounds of 4
        needed = int(last.split("queue_depth >= ")[1].split()[0])
        assert needed % 4 == 0 and needed > depth * 100
