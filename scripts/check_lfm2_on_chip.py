#!/usr/bin/env python3
"""LFM2-8B-A1B at the benchmark's configuration on the chip, outside any
timed window (model-configs section 3):

    chiprun -- python scripts/check_lfm2_on_chip.py
        [--config lfm2-8b-a1b-d12] [--sequences 3] [--seed 1] [--tiny]

Everything `scripts/check_solar_open2_on_chip.py` reads of a served model
(the log-probabilities through `score_logprobs`, chunks of 1,024 that carry
a row's convolution tails from chunk to chunk; the one-token steps
teacher-forced over the 3 pools of pages and the 9 tail pools; the greedy
deficit), for this family's reference and its controls: every matrix and
every normed input in float8's 3 bits of mantissa (the precision below the
stated one) and the structural ones of `tests/unit/test_lfm2.py`, each a
wrong model that has to read over the traffic file's limit or be reported as
one the limit cannot tell (two are, `FAMILY["untold"]`): the choice-only
bias added to the weights; no renormalisation; the tail not carried over a chunk boundary; the B gate left
out; the C gate left out; the norm over the whole projection instead of a
head; experts in the leading layers. Prints that script's JSON line and
writes chiprun_out/lfm2_check.json.

The reference's float32 pass fits beside the 7.86 GB of bfloat16 weights: it
casts one expert at a time, attends a head at a time over the 8,320
positions, and the served engine and its arena are let go first.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))


def controls(jnp):
    return {"all-in-float8": {"mantissa_bits": 3},
            "bias-added-to-the-weights": {"bias_in_weights": True},
            "no-renormalisation": {"norm_topk_prob": False},
            "tail-not-carried-over-a-chunk": {"tail_cut": 1024},
            "no-B-gate": {"gate_b": False},
            "no-C-gate": {"gate_c": False},
            "norm-over-the-whole-projection": {"norm_per_head": False},
            "experts-in-the-leading-layers":
                {"experts_in_leading_layers": True}}


FAMILY = {"config": "lfm2-8b-a1b-d12", "traffic": "serve-docs-r16",
          "controls": controls,
          # read and reported, not deciding `ok`: two that no limit on
          # logits can tell in a bfloat16 model with RANDOM weights, both
          # told at float32 (tests/unit/test_lfm2.py: 1.7e-4 and 1e-2
          # against a limit of 1e-5). A bias of std 0.01 under a
          # renormalisation moves the weights by less than bfloat16's own
          # rounding. And with norm scales of 1 and heads drawn alike, a
          # head's RMS is the whole projection's to a tenth, so the norm
          # over the projection is the norm a head with each head's scores
          # scaled by 0.9-1.1, on attention that random keys leave nearly
          # uniform (PERF.md section 7: a trained model would tell both)
          "untold": ("bias-added-to-the-weights",
                     "norm-over-the-whole-projection"),
          # the cell's own lengths: prompts 4,096-8,192, answers 32-64;
          # every sequence padded to max_model_len for the reference
          "lengths": (4096, 8192, 32, 64, 8320),
          "out": "lfm2_check.json"}


def main(argv=None):
    import check_solar_open2_on_chip as served

    return served.main(sys.argv[1:] if argv is None else argv, family=FAMILY)


if __name__ == "__main__":
    sys.exit(main())
