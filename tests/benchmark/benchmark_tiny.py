"""A benchmark root of tiny cells in a temporary directory: the real
files with their sizes cut, so that the trainers run on the CPU in
seconds.  Sizes and the platform are steered here, in the tests; the
benchmark has no option for either."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import spec  # noqa: E402

TINY = {
    "bert": dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 intermediate_size=128, vocab_size=512,
                 max_position_embeddings=128,
                 check_leaves=["encoder/layer_0/attention/query/kernel",
                               "encoder/layer_1/output/kernel",
                               "encoder/word_embeddings/embedding"]),
    "gpt": dict(n_embd=64, n_layer=2, n_head=4, vocab_size=512,
                n_positions=128, n_ctx=128,
                check_leaves=["layer_0/attention/query/kernel",
                              "layer_1/output/kernel",
                              "word_embeddings/embedding"]),
}


def _read(*parts):
    with open(os.path.join(spec.HERE, *parts)) as f:
        return json.load(f)


def _write(obj, root, *parts):
    path = os.path.join(root, *parts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_config(family: str) -> dict:
    """The first real configuration of ``family``, cut to a toy."""
    for name in sorted(os.listdir(os.path.join(spec.HERE, "configs"))):
        config = _read("configs", name)
        if config["family"] == family:
            config.update(TINY[family])
            return config
    raise KeyError(family)


def make_root(root: str, family: str, trainer: str, processes: int = 1,
              chips: int = 0, **traffic) -> str:
    """Write one tiny cell under ``root`` and return its name."""
    mix = next(_read("traffic", f)
               for f in sorted(os.listdir(os.path.join(spec.HERE,
                                                       "traffic")))
               if _read("traffic", f)["trainer"] == trainer)
    mix.update(batch_per_chip=8, seq_len=32, processes=processes,
               warmup_steps=3, local_steps=2, traced_steps=2)
    mix.update(traffic)
    _write(tiny_config(family), root, "configs", family + "-tiny.json")
    _write(mix, root, "traffic", trainer + "_tiny.json")
    name = "%s-tiny_%s" % (family, trainer)
    _write({"config": family + "-tiny", "traffic": trainer + "_tiny",
            "chips": chips or processes,
            "loss_band": {"step": 8, "low": None, "high": None}},
           root, "workloads", name + ".json")
    link = os.path.join(root, "layer_metrics")
    if not os.path.exists(link):
        os.symlink(os.path.join(spec.HERE, "layer_metrics"), link)
    return name
