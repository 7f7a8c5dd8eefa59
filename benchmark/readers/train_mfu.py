"""Model FLOP/s utilisation of the training window: tokens per second times
the operations a token requires (forward and backward, recomputation not
counted; the family's `train_flops_per_token`), over chips times the
published bf16 peak of the attached device kind (`benchmark/peaks.json`)."""

from benchmark import device


def read(run):
    tok_s = run.extras.get("train_tok_s")
    if tok_s is None:
        return None
    peak = device.peaks(run.device["kind"])["bf16_flops_per_s"]
    return 100.0 * tok_s * run.extras["flops_per_token"] / (
        run.cell.chips * peak)
