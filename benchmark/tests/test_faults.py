"""A whole run with the timed path broken underneath comes out not
correct, once for each fault this kind of cell can have. The run is the
harness's own (``run.run_cell``) past its look for a chip, at the
rehearsal's tiny size; the sound run beside them comes out correct."""

import pytest

from benchmark import run


def drive(rehearsal, seed=11, workload="vit-h14.backlog", **kw):
    return run.run_cell(workload, seed, 1.5, False, rehearse=rehearsal, **kw)


def test_sound_run_is_correct(rehearsal):
    line = drive(rehearsal)
    assert line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == line["compared"]["spans_compared"]["value"]
    assert list(line)[-1] == "compared"


def test_paced_run_is_correct(rehearsal):
    line = drive(rehearsal, workload="vit-h14.steady")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"latency_p95_ms", "setup_s"}


def test_an_answer_altered_where_it_is_produced(rehearsal, monkeypatch):
    from odigos_tpu.serving.engine import SequenceBackend

    sound = SequenceBackend.harvest

    def altered(self, handle):
        scores = sound(self, handle)
        scores[0] = min(1.0, scores[0] + 0.3)    # one span of each call
        return scores

    monkeypatch.setattr(SequenceBackend, "harvest", altered)
    line = drive(rehearsal)
    assert line["correct"] is False
    assert line["compared"]["gap_max"]["value"] > \
        line["compared"]["gap_max"]["limit"]
    assert line["compared"]["delivery_faults"]["value"] == 0


def test_one_shard_of_a_call_left_out(rehearsal, monkeypatch):
    """What a data-parallel call whose gather leaves a chip's rows out
    would hand back: the last quarter of every call's scores is zero."""
    from odigos_tpu.serving.engine import SequenceBackend

    sound = SequenceBackend.harvest

    def partial(self, handle):
        scores = sound(self, handle)
        scores[len(scores) - len(scores) // 4:] = 0.0
        return scores

    monkeypatch.setattr(SequenceBackend, "harvest", partial)
    line = drive(rehearsal)
    assert line["correct"] is False
    assert line["compared"]["gap_max"]["value"] > \
        line["compared"]["gap_max"]["limit"]


def test_a_span_that_never_arrives(rehearsal, monkeypatch):
    from odigos_tpu.serving import fastpath

    sound = fastpath.tag_anomalies
    state = {"frames": 0}

    def lossy(batch, scores, threshold):
        out = sound(batch, scores, threshold)
        state["frames"] += 1
        if state["frames"] == 9:      # one frame, past the warm-up's six
            return out.slice(0, len(out) - 1)
        return out

    monkeypatch.setattr(fastpath, "tag_anomalies", lossy)
    line = drive(rehearsal)
    assert line["correct"] is False
    assert line["compared"]["delivery_faults"]["value"] >= 1
    assert line["failed"] >= 1


def test_a_span_that_arrives_unscored_is_failed_not_wrong(rehearsal,
                                                          monkeypatch):
    from odigos_tpu.serving import fastpath

    sound = fastpath.tag_anomalies
    state = {"frames": 0}

    def shy(batch, scores, threshold):
        state["frames"] += 1
        if state["frames"] == 9:
            return batch                  # forwarded untagged
        return sound(batch, scores, threshold)

    monkeypatch.setattr(fastpath, "tag_anomalies", shy)
    line = drive(rehearsal)
    assert line["failed"] >= 1
    assert line["compared"]["delivery_faults"]["value"] == 0
    assert line["correct"] is True


def test_refuses_off_the_chip_and_prints_no_result(capsys):
    assert run.main(["--workload", "vit-h14.backlog", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
