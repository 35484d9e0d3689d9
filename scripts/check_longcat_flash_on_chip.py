#!/usr/bin/env python3
"""LongCat-Flash-Chat at the benchmark's configuration on the chip, outside
any timed window (model-configs section 3):

    chiprun -- python scripts/check_longcat_flash_on_chip.py
        [--config longcat-flash-chat-ep32-d4] [--sequences 3] [--seed 1]
        [--tiny]

Everything `scripts/check_solar_open2_on_chip.py` reads of a served model
(the log-probabilities through `score_logprobs`, whose chunks read the latent
pools EXPANDED; the one-token steps teacher-forced over the 8 pools, which
read them ABSORBED; the greedy deficit), for this family's reference and its
controls: every matrix and every normed input in float8's 3 bits of mantissa
(the precision below the stated one) and the structural ones, each a wrong
model that has to read over the traffic file's limit or be reported as one
the limit cannot tell: no scale on the latent; no scale on the query; the
factor not on zero-computation weights; the shortcut joined behind the FIRST
sublayer; renormalised weights; rope on all 192 values of a head. Prints
that script's JSON line and writes chiprun_out/longcat_flash_check.json.

The reference's float32 pass fits beside the 10.35 GB of bfloat16 weights: it
casts one matrix (one expert) at a time, attends a head at a time over the
5,120 positions, and the served engine and its arena are let go first.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))


def controls(jnp):
    return {"all-in-float8": {"mantissa_bits": 3},
            "no-scale-on-c": {"scale_c": False},
            "no-scale-on-q": {"scale_q": False},
            "zero-weights-without-the-factor": {"zero_weight_scaled": False},
            "shortcut-joined-after-the-first-sublayer": {"join_after": 0},
            "renormalised-weights": {"renormalise": True},
            "rope-on-all-of-a-head": {"rope_all": True}}


FAMILY = {"config": "longcat-flash-chat-ep32-d4",
          "traffic": "serve-ctx4k-r32", "controls": controls,
          "untold": (),
          # the cell's own lengths: prompts 3,072-4,096, answers 768-1,024;
          # every sequence padded to max_model_len for the reference
          "lengths": (3072, 4096, 768, 1024, 5120),
          "out": "longcat_flash_check.json"}


def main(argv=None):
    import check_solar_open2_on_chip as served

    return served.main(sys.argv[1:] if argv is None else argv, family=FAMILY)


if __name__ == "__main__":
    sys.exit(main())
