"""The model directory a run serves from: `config.json` from the
configuration's file, and a synthetic `tokenizer.json`.

The tokenizer is a word-level vocabulary of exactly `vocab_size`
distinct words (`t0` .. `t<V-1>`, id = the number), split on
whitespace, with no EOS, BOS or pad token. So the server loads it
through the path deployments take (`transformers`), every generated id
decodes to a non-empty piece of text and is streamed as a chunk of its
own, no stop id exists, and a prompt of n words is n tokens. Stdlib
only: the parent of a run never imports JAX or `transformers`.
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict, List, Sequence

# keys of a configuration's file that are the benchmark's own and not
# part of the model's published config.json
NOT_MODEL_KEYS = ("source", "reduced", "assumed", "benchmark")


def word(token_id: int) -> str:
    return f"t{token_id}"


def token_id(text: str) -> int:
    """Inverse of `word`; raises ValueError for anything else."""
    text = text.strip()
    if not text.startswith("t"):
        raise ValueError(f"not a word of the vocabulary: {text!r}")
    return int(text[1:])


def tokenizer_json(vocab_size: int) -> Dict:
    return {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel",
                  "vocab": {word(i): i for i in range(vocab_size)},
                  "unk_token": word(0)},
    }


def model_config(config_file: Dict) -> Dict:
    return {k: v for k, v in config_file.items()
            if k not in NOT_MODEL_KEYS}


def write(model_dir: str, config_file: Dict) -> None:
    os.makedirs(model_dir, exist_ok=True)
    cfg = model_config(config_file)
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(cfg, f, indent=1)
    with open(os.path.join(model_dir, "tokenizer.json"), "w") as f:
        json.dump(tokenizer_json(cfg["vocab_size"]), f)
    with open(os.path.join(model_dir, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)


def prompt_ids(rng: random.Random, n: int, vocab_size: int) -> List[int]:
    """n seeded ids, unrelated from prompt to prompt, so the prefix
    cache never hits and no suffix program compiles mid-window."""
    return [rng.randrange(vocab_size) for _ in range(n)]


def prompt_text(ids: Sequence[int]) -> str:
    return " ".join(word(i) for i in ids)
