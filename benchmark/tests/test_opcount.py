"""The operation count of every architecture a configuration names
against the hand count its case file states (``arch_cases/<name>.py``),
and the whole against the parts."""

import pytest

from benchmark import opcount
from benchmark.tests.conftest import ARCHITECTURES, arch_case


@pytest.fixture(params=ARCHITECTURES)
def arch_and_case(request):
    return arch_case(request.param)


def test_hand_count_by_part(arch_and_case):
    arch, case = arch_and_case
    got = arch.flops_by_part(case.TINY, case.HAND["pieces"])
    assert got == case.HAND["by_part"]


def test_the_parts_sum_to_the_whole_and_fold_the_scopes(arch_and_case):
    arch, case = arch_and_case
    pieces = [64, 64, 17, 3, 1]
    for model, _ in case.PUBLISHED:
        by = arch.flops_by_part(model, pieces)
        assert opcount.flops_needed(arch, model, pieces) \
            == pytest.approx(sum(by.values()))
        assert all(v > 0 for v in by.values())
        # every scope folds into a part that is counted, and operations
        # under no scope have a part to fall into
        assert set(arch.PARTS.values()) <= set(by) and "rest" in by


def test_needed_counts_real_spans_only(arch_and_case):
    """Additive over pieces, and more than additive in a piece's length
    only through attention: two pieces of 3 and 1 need less than one of 4."""
    arch, case = arch_and_case
    three, one = (opcount.flops_needed(arch, case.TINY, [n]) for n in (3, 1))
    assert opcount.flops_needed(arch, case.TINY, [3, 1]) == three + one
    assert opcount.flops_needed(arch, case.TINY, [4]) > three + one
    assert opcount.flops_needed(arch, case.TINY, []) == 0


def test_published_sizes(arch_and_case):
    arch, case = arch_and_case
    for model, per_span in case.PUBLISHED:
        assert opcount.flops_needed(arch, model, [1]) \
            == pytest.approx(per_span, rel=1e-2)


def test_unknown_device_kind_is_an_error():
    assert opcount.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        opcount.peaks("TPU v99")
