"""The served scores are judged by the equations the configuration names,
and by no others: with the configuration's own architecture a sound run
is correct; with a double laid over it (``encoder_twice.py``: the same
stack applied twice, counted twice) the same program's scores are not,
and the operations needed read twice the block terms. The double lives
among the tests and took no edit to the harness."""

import os

import pytest

from benchmark import architectures, run

DOUBLE = "benchmark/tests/encoder_twice.py"


def test_the_double_is_not_correct_where_the_own_architecture_is(
        rehearsal, stood_in_trace):
    """The paced cell sends the same frames on both runs (20 a second for
    a second, from the pool frame the seed picks), so the traces scored
    are the same and the counts compare exactly."""
    own = run.run_cell("vit-h14.steady", 41, 1.0, True, rehearse=rehearsal)
    twice = run.run_cell("vit-h14.steady", 41, 1.0, True,
                         rehearse={**rehearsal, "architecture": DOUBLE})
    assert own["correct"] is True and own["failed"] == 0
    assert twice["correct"] is False and twice["failed"] == 0
    assert own["attempted"] == twice["attempted"]
    gap = twice["compared"]["gap_max"]
    assert gap["limit"] == rehearsal["correct"]["gap_max"]
    assert gap["value"] > 3 * gap["limit"]
    assert twice["compared"]["delivery_faults"]["value"] == 0
    a, b = own["hosttrace"]["parts"], twice["hosttrace"]["parts"]
    for part in ("attn", "mlp"):
        assert b[part]["flops_needed"] == 2 * a[part]["flops_needed"] > 0
        assert b[part]["peak_share_needed"] \
            == pytest.approx(2 * a[part]["peak_share_needed"])
    assert b["rest"]["flops_needed"] == a["rest"]["flops_needed"] > 0


def test_an_architecture_is_found_by_the_name_the_configuration_gives():
    _, _, config, _ = run.load_cell("vit-h14.backlog")
    arch = run.load_architecture(config)
    assert arch.__file__ == os.path.join(
        run.HERE, "architectures", config["architecture"] + ".py")
    for name in architectures.EXPORTS:
        assert hasattr(arch, name)
    assert callable(arch.scores) and callable(arch.flops_by_part)
    assert arch.__doc__.strip()


def test_no_architecture_is_refused_with_the_path_looked_for():
    with pytest.raises(run.Refused, match=r"architectures/<name>\.py"):
        run.load_architecture({"name": "bare"})
    with pytest.raises(run.Refused) as e:
        run.load_architecture({"name": "x", "architecture": "no_such"})
    assert os.path.join(run.HERE, "architectures", "no_such.py") \
        in str(e.value)
    for bad in ("../tests/encoder_twice", "a b", "", 7, "x" * 65):
        with pytest.raises(run.Refused):
            run.load_architecture({"name": "x", "architecture": bad})


def test_only_a_rehearsal_may_give_a_path():
    with pytest.raises(run.Refused):
        run.load_architecture({"name": "x", "architecture": DOUBLE})
    arch = run.load_architecture({"architecture": "encoder_preln"},
                                 {"architecture": DOUBLE})
    assert arch.__file__ == os.path.join(run.ROOT, DOUBLE)
    with pytest.raises(run.Refused, match="no_such.py"):
        run.load_architecture({}, {"architecture": "benchmark/no_such.py"})


def test_a_file_that_lacks_an_export_is_refused(tmp_path, monkeypatch):
    """The checkout stood in for by an empty directory, so that the half
    file is under it and nothing is written into the real one."""
    monkeypatch.setattr(architectures, "ROOT", str(tmp_path))
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "half.py").write_text(
        '"""Equations."""\nPARTS = {}\nCONTROL = "fp8"\n'
        'def scores(frames, seed, model): return []\n')
    with pytest.raises(run.Refused, match="flops_by_part"):
        run.load_architecture({}, {"architecture": "sub/half.py"})


@pytest.mark.parametrize("path", [
    "../outside.py", "benchmark/../../outside.py", "/etc/hostname",
    "benchmark/tests/../../../outside.py"])
def test_a_path_that_leads_out_of_the_checkout_is_refused(path, tmp_path,
                                                          monkeypatch):
    """A rehearsal's path loads and runs a file: only one under the
    checkout, whether or not the file outside exists."""
    inner = tmp_path / "checkout"
    (inner / "benchmark" / "tests").mkdir(parents=True)
    (tmp_path / "outside.py").write_text("raise SystemExit('ran')\n")
    monkeypatch.setattr(architectures, "ROOT", str(inner))
    with pytest.raises(run.Refused, match="out of the checkout"):
        run.load_architecture({}, {"architecture": path})


def test_a_link_that_leads_out_of_the_checkout_is_refused(tmp_path,
                                                          monkeypatch):
    inner = tmp_path / "checkout"
    inner.mkdir()
    (tmp_path / "outside.py").write_text("raise SystemExit('ran')\n")
    (inner / "link.py").symlink_to(tmp_path / "outside.py")
    monkeypatch.setattr(architectures, "ROOT", str(inner))
    with pytest.raises(run.Refused, match="out of the checkout"):
        run.load_architecture({}, {"architecture": "./link.py"})
