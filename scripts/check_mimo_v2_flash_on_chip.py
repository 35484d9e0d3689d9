#!/usr/bin/env python3
"""MiMo-V2-Flash at the benchmark's configuration on the chip, outside any
timed window (model-configs section 3):

    chiprun -- python scripts/check_mimo_v2_flash_on_chip.py
        [--config mimo-v2-flash-ep16-d7] [--sequences 2] [--seed 1] [--tiny]

Everything `scripts/check_solar_open2_on_chip.py` reads of a served model
(the log-probabilities through `score_logprobs`: chunks of 1,024 through the
prefill kernel over the rings and the pages, then the last 64 tokens a step
at a time; the one-token steps teacher-forced through `window_decode_
attention` over the five rings and `full_kv_decode_attention` over the two
pools; the greedy deficit), on sequences of 8k tokens and more, for this
family's reference and its controls: every matrix and every normed input in
float8's 3 bits of mantissa (the precision below the stated one) and the
structural ones, each a wrong model that has to read over the traffic file's
limit or be reported as one the limit cannot tell (`untold`: the two windows
and the bias, which the CPU tests hold instead): no sink; a sink on the
full layers too; a window of 127 or of 129 keys; the full layers' 4
key-value heads in the window layers, or the window layers' 8 in the full
ones; rope on all 192 values of a head; the window layers' rope base in the
full layers; values not scaled by 0.707; weights not renormalised; the
choice-only bias in the weights. Prints that script's JSON line and writes
chiprun_out/mimo_v2_flash_check.json.

The reference's float32 pass fits beside the 6.9 GB of bfloat16 weights: it
casts a layer's matrices (one expert) at a time, attends a head at a time in
blocks of 512 queries over the 10,240 positions, and the served engine and
its arena are let go first.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))


def controls(jnp):
    return {"all-in-float8": {"mantissa_bits": 3},
            "no-sink": {"add_swa_attention_sink_bias": False},
            "a-sink-on-the-full-layers-too": {
                "add_full_attention_sink_bias": True},
            "a-window-of-127-keys": {"sliding_window": 127},
            "a-window-of-129-keys": {"sliding_window": 129},
            "4-key-value-heads-in-the-window-layers": {
                "swa_num_key_value_heads": 4},
            "8-key-value-heads-in-the-full-layers": {
                "num_key_value_heads": 8},
            "rope-on-all-of-a-head": {"rope_all": True},
            "the-window-layers-rope-base-in-the-full-layers": {
                "rope_theta": 10000},
            "values-not-scaled": {"attention_value_scale": 1.0},
            "weights-not-renormalised": {"norm_topk_prob": False},
            "bias-added-to-the-weights": {"bias_in_weights": True}}


FAMILY = {"config": "mimo-v2-flash-ep16-d7",
          "traffic": "serve-ctx8k-r32", "controls": controls,
          # what no limit on logits can tell in a bfloat16 model (one key of
          # 128 more or less; a bias of std 0.01 under a renormalisation):
          # read and reported, held by the CPU tests at 1e-5, and they do
          # not decide `ok` (PERF.md section 7 bo)
          "untold": ("a-window-of-127-keys", "a-window-of-129-keys",
                     "bias-added-to-the-weights"),
          # the cell's own lengths: prompts 6,144-8,192, answers
          # 1,536-2,048; every sequence padded to max_model_len for the
          # reference
          "lengths": (6144, 8192, 1536, 2048, 10240),
          "out": "mimo_v2_flash_check.json"}


def main(argv=None):
    import check_solar_open2_on_chip as served

    return served.main(sys.argv[1:] if argv is None else argv, family=FAMILY)


if __name__ == "__main__":
    sys.exit(main())
