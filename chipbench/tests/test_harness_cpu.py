"""A whole run on the CPU at a small size, with the look for a chip
skipped: the sound program reads `correct`; the control, and each fault
planted underneath the harness after warm-up, read not correct."""
from __future__ import annotations

import pytest

import control
import harness
import run
import small


def _break_token(eng):
    """Every decode step's first row returns another token."""
    decode = eng.ex.decode
    vocab = eng.cfg.vocab_size

    def bad_decode(tokens, tables, kv_lens):
        out = decode(tokens, tables, kv_lens)
        out[0] = (out[0] + 1) % vocab
        return out
    eng.ex.decode = bad_decode


def _break_kv_write(eng):
    """The prefill's layer writes into both pools are dropped."""
    eng.ex.write_layer = lambda *a, **k: None


def _break_reload(eng):
    """Offloaded layers are never copied back host to device: the device
    blocks a reloaded layer maps keep whatever they held."""
    copy_blocks = eng.ex.copy_blocks

    def bad_copy(src_tier, dst_tier, src_ids, dst_ids):
        if (src_tier, dst_tier) != ("host", "device"):
            copy_blocks(src_tier, dst_tier, src_ids, dst_ids)
    eng.ex.copy_blocks = bad_copy


FAULTS = {"token": _break_token, "kv_write": _break_kv_write,
          "reload": _break_reload}


def test_sound_run_is_correct_and_reports_its_metrics(monkeypatch):
    res, _ = small.tiny_run(2**31 + 11, monkeypatch)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tbt_mean_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= 1e-3
    # layers were offloaded and brought back, and the sample holds such a
    # request
    assert res["sampled"]["reloaded_in_run"] > 0
    assert res["sampled"]["reloaded_tokens"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_reads_not_correct(fault, monkeypatch):
    warm_up = harness.warm_up

    def warm_then_break(eng, cfile, reqs):
        out = warm_up(eng, cfile, reqs)
        FAULTS[fault](eng)
        return out
    monkeypatch.setattr(harness, "warm_up", warm_then_break)
    res, _ = small.tiny_run(5, monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > 0.05


def test_control_reads_not_correct(monkeypatch):
    out = control.read_seed(*small.tiny_inputs(7, monkeypatch))
    assert out["correct"] is True
    assert out["control_correct"] is False
    assert out["control_gap"] > out["limit"] >= out["program_gap"]


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit):
        run.main(["--workload", "granite-3-2b.chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert capsys.readouterr().out == ""
