#!/usr/bin/env python3
"""Ouro-2.6B at the benchmark's configuration on the chip, outside any timed
window (model-configs section 3):

    chiprun -- python scripts/check_ouro_on_chip.py
        [--config ouro-2.6b] [--sequences 3] [--seed 1] [--tiny]

Everything `scripts/check_solar_open2_on_chip.py` reads of a served model
(the log-probabilities through `score_logprobs`, the one-token steps
teacher-forced over the 192 pools, the greedy deficit), for this family's
reference and its controls: every matrix and every half's normed input in
float8's 3 bits of mantissa (the precision below the stated one) and the
structural ones, each a wrong model that has to read over the traffic
file's limit or be reported as one the limit cannot tell: three passes for
four; pass t reading the keys of pass t-1; every pass reading the last
pass's keys (the paper's decode-time sharing, which is not the published
configuration); no norm behind the halves; the closing norm once, at the
end. Prints that script's JSON line and writes chiprun_out/ouro_check.json.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))


def controls(jnp):
    return {"all-in-float8": {"mantissa_bits": 3},
            "three-passes-for-four": {"controls": ("one_pass_fewer",)},
            "the-pool-of-the-pass-before": {"controls": ("previous_pool",)},
            "every-pass-on-the-last-pool": {"controls": ("last_pool",)},
            "no-norm-behind-the-halves": {"controls": ("no_post_norms",)},
            "the-closing-norm-once": {"controls": ("final_norm_once",)}}


FAMILY = {"config": "ouro-2.6b", "traffic": "serve-short-r16",
          "controls": controls,
          # the limit tells every one of them (the least, every pass on the
          # last pool, reads 1.95 for a limit of 1.5; my chip run, PR 60)
          "untold": (),
          # the cell's own lengths: prompts 32-128, answers of 64-192 tokens;
          # every sequence padded to max_model_len for the reference
          "lengths": (32, 128, 64, 192, 320),
          "out": "ouro_check.json"}


def main(argv=None):
    import check_solar_open2_on_chip as served

    return served.main(sys.argv[1:] if argv is None else argv, family=FAMILY)


if __name__ == "__main__":
    sys.exit(main())
