"""A throwaway benchmark root at a size the CPU runs in seconds.

It holds its own ``BENCHMARK.json``, a two-layer configuration, two mixes
and copies of the benchmark's metric readers, laid out as in the
repository, so ``bench.run.execute`` finds everything by name there.
"""

import json
import os
import shutil
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CONFIG = {
    "name": "tiny", "arch": "dense", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "torch_dtype": "bfloat16", "qk_norm": True,
    "serving": {"batch": 4, "max_len": 256, "page_size": 16,
                "n_pages": 33, "chunk": 32},
    # Readings at this size on the CPU, seeds 1-3 and 6-15: the program's
    # widest gap at most 0.0131, the fp8 control's at least 0.0955.
    "limits": {"served_logit_gap": 0.05},
}
# Fewer slots than pages in a chunk: the warm-up must still reach every
# page count a chunk can take.
NARROW = dict(CONFIG, name="narrow",
              serving={"batch": 2, "max_len": 256, "page_size": 16,
                       "n_pages": 33, "chunk": 64})
CHAT = {"arrivals": "poisson", "block": 8, "block_s": 0.5,
        "prompt_tokens": {"lo": 4, "hi": 100},
        "output_tokens": {"lo": 4, "hi": 32}, "max_tokens": 256,
        "ramp_s": 1.0, "window": "after_ramp", "check_tokens": 60,
        "check_requests": 4}
DOC = {"arrivals": "backlog", "requests": 160, "block": 4,
       "prompt_tokens": {"lo": 60, "hi": 200},
       "output_tokens": {"lo": 8, "hi": 40}, "max_tokens": 256,
       "window": "first_finish", "check_tokens": 60, "check_requests": 4}


def _e2e(name, unit, better, cells=None):
    m = {"name": name, "unit": unit, "better": better, "bound": 0.25,
         "source": "host_clock"}
    if cells:
        m["workloads"] = cells
    return m


BENCHMARK = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 2,
    "configs": [{"name": "tiny", "source": "test", "reduced": [],
                 "file": "bench/configs/tiny.json", "why": "test"},
                {"name": "narrow", "source": "test", "reduced": [],
                 "file": "bench/configs/narrow.json", "why": "test"}],
    "workloads": [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tchat",
         "chips": 1, "why": "test"},
        {"name": "tiny.doc", "config": "tiny", "traffic": "tdoc",
         "chips": 1, "why": "test"},
        {"name": "narrow.doc", "config": "narrow", "traffic": "tdoc",
         "chips": 1, "why": "test"}],
    "end_to_end": [
        _e2e("setup_s", "s", "lower"),
        _e2e("ttft_p90_ms", "ms", "lower", ["tiny.chat"]),
        _e2e("tpot_p90_ms", "ms", "lower", ["tiny.chat"]),
        _e2e("out_tok_s", "tokens/s", "higher")],
    "per_layer": [
        {"name": "queue_wait_p90_ms.chat", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "scheduler",
         "moves": "ttft_p90_ms", "workloads": ["tiny.chat"]}],
}


def make_root(tmp) -> str:
    root = str(tmp)
    for d in ("configs", "traffic"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"),
                    dirs_exist_ok=True)
    for rel, obj in (("BENCHMARK.json", BENCHMARK),
                     ("bench/configs/tiny.json", CONFIG),
                     ("bench/configs/narrow.json", NARROW),
                     ("bench/traffic/tchat.json", CHAT),
                     ("bench/traffic/tdoc.json", DOC)):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    return root
