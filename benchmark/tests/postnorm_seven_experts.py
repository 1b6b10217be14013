"""A test's double, in no configuration and no entry of BENCHMARK.json:
``postnorm_moe_decoder`` with one routed expert a span fewer (the
``experts_per_span`` - 1 largest biased scores of all ``n_experts``, the
weights normalised over those, the same held share computed). A
rehearsal lays it over a configuration's architecture by its path
(``benchmark/tests/postnorm_seven_experts.json``); the program still
serves ``experts_per_span`` experts, so judged by these equations its
scores are not correct: the fault a build makes that leaves one expert a
span out, read through the harness's own window and judge."""

from benchmark import architectures

base = architectures.load("postnorm_moe_decoder")
PARTS, CONTROL, flops_by_part = base.PARTS, base.CONTROL, base.flops_by_part


def scores(frames, seed, model, precision="float32", block_rows=128):
    fewer = {**model, "experts_per_span": int(model["experts_per_span"]) - 1}
    return base.scores(frames, seed, fewer, precision, block_rows)
