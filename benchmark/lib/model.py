"""Configuration files → the program's model object. The file's top level
holds the published ``config.json`` keys as they are run; the program's
own HF reader (``models/hf_loader.config_from_hf``) turns them into its
DecoderConfig — the path a user loading that model takes."""

import json
import os

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(name: str) -> dict:
    path = os.path.join(_HERE, "configs", f"{name}.json")
    with open(path) as fh:
        return json.load(fh)


#: ``--rehearse`` only: tiny widths (head size 128 kept so the same kernel
#: family is selected), never a cell
REHEARSAL_KEYS = {"hidden_size": 256, "intermediate_size": 512,
                  "num_attention_heads": 2, "num_key_value_heads": 1,
                  "num_hidden_layers": 2, "vocab_size": 512}


def build_model(conf: dict, rehearse: bool = False):
    from deepspeed_tpu.models.hf_loader import config_from_hf
    hf = {k: v for k, v in conf.items()
          if not isinstance(v, (dict, list)) or k == "architectures"}
    if rehearse:
        hf.update(REHEARSAL_KEYS)
    model = config_from_hf(hf)
    # the file is the configuration as run: refuse a reader that drops a
    # width on the way
    want = {"hidden_size": model.hidden_size,
            "intermediate_size": model.intermediate_size,
            "num_attention_heads": model.num_heads,
            "num_key_value_heads": model.kv_heads,
            "num_hidden_layers": model.num_layers,
            "vocab_size": model.vocab_size,
            "sliding_window": model.sliding_window,
            "rms_norm_eps": model.norm_eps,
            "rope_theta": model.rope_theta}
    for key, got in want.items():
        if key in hf and hf[key] != got:
            raise ValueError(f"config key {key}: file says {hf[key]!r}, "
                             f"the program built {got!r}")
    return model


def reference_widths(conf: dict, rehearse: bool = False) -> dict:
    """The published keys the plain reference is built from (the file's,
    not the program's model object)."""
    return dict(conf, **(REHEARSAL_KEYS if rehearse else {}))


def prng_key(seed: int):
    """A jax key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
