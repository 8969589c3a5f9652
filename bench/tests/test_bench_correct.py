"""The check that decides ``correct``, on a tiny copy of a cell on the CPU:
a sound run passes, and a run with its timed path broken underneath
(a round that leaves the weights unchanged, half of each batch left out,
a knowledge row altered where it is produced) fails, as does, on a TPU, the
control (the reference at ``high`` precision in the program's place). The
harness's look for a chip is skipped; the rest of a run is the
benchmark's own."""
import jax.numpy as jnp
import pytest

from tiny import make_root
import control
import run
import spec

SEED = 2**31 + 5


def _run(tmp_path):
    root = make_root(tmp_path)
    cell = spec.load_cell("tiny", root)
    return run.measure(cell, SEED, 0.01, False, require_tpu=False, root=root,
                       cache=False)


def _over(line):
    return [k for k, v in line["checked"].items() if not v["value"] <= v["limit"]]


def test_sound_run_is_correct(tmp_path):
    line = _run(tmp_path)
    assert line["correct"], line["checked"]
    assert set(line["metrics"]) >= {"setup_s", "round_s"}


def test_round_that_leaves_weights_unchanged(tmp_path, monkeypatch):
    import repro.fl.server as server
    real = server.server_round

    def unchanged(model, prev_global, *a, **kw):
        res = real(model, prev_global, *a, **kw)
        res.global_params = prev_global
        return res

    monkeypatch.setattr(server, "server_round", unchanged)
    line = _run(tmp_path)
    assert not line["correct"]
    assert "first_update_gap" in _over(line)


def test_half_of_each_batch_left_out(tmp_path, monkeypatch):
    import repro.core.rounds as rounds
    real = rounds.local_batches

    def half(x, y, k, cfg):
        bx, by = real(x, y, k, cfg)
        h = bx.shape[1] // 2
        return bx[:, :h], by[:, :h]

    monkeypatch.setattr(rounds, "local_batches", half)
    line = _run(tmp_path)
    assert not line["correct"]


def test_knowledge_row_altered(tmp_path, monkeypatch):
    from repro.fl.transport.channel import Channel
    real = Channel.upload_knowledge

    def altered(self, cid, acts, labels, valid, codec, pre=None):
        first = int(jnp.argmax(jnp.asarray(valid)))
        acts = jnp.asarray(acts).at[first].multiply(-1.0)
        return real(self, cid, acts, labels, valid, codec, pre=pre)

    monkeypatch.setattr(Channel, "upload_knowledge", altered)
    line = _run(tmp_path)
    assert not line["correct"]
    assert "knowledge_gap_levels" in _over(line)


def test_control_is_not_correct(tmp_path):
    """At a test's size the control departs from the reference by far more
    than a sound run does (its readings at the cells' own sizes, taken on
    the chip with ``bench/control.py``, fail the cells' limits)."""
    import jax
    if jax.default_backend() != "tpu":
        pytest.skip("the CPU computes float32 products alike at every "
                    "matmul precision, so the control equals the reference "
                    "there; run this file on a TPU")
    sound = _run(tmp_path / "sound")["checked"]
    root = make_root(tmp_path / "control")
    cell = spec.load_cell("tiny", root)
    numbers, _ = control.control_numbers(cell, SEED, rounds=2)
    apart = [k for k, v in numbers.items() if k in sound
             and v > 3 * sound[k]["value"] and v > 0]
    assert apart, (numbers, sound)
