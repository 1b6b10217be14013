"""A test's double, in no configuration and no entry of BENCHMARK.json:
``encoder_preln`` with its stack of layers applied twice over the same
weights (x -> stack(stack(x))), and the operation count to match. A
rehearsal lays it over a configuration's architecture by its path; the
program still serves ``encoder_preln``, so judged by these equations its
scores are not correct."""

from benchmark import architectures, reference

base = architectures.load("encoder_preln")
PARTS, CONTROL = base.PARTS, base.CONTROL


def scores(frames, seed, model, precision="float32", block_rows=256):
    embed, stack, head = base.encoder(seed, model, precision)
    return reference.score_rows(
        frames, int(model["max_len"]), block_rows,
        lambda cat, cont, seg, pos: head(
            stack(stack(embed(cat, cont, seg, pos), seg), seg)))


def flops_by_part(model, piece_lengths):
    by = base.flops_by_part(model, piece_lengths)
    return {**by, "attn": 2 * by["attn"], "mlp": 2 * by["mlp"]}
