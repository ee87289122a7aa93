"""Configuration files → the program's model object, and the plain
reference that checks it. The file's top level holds the published
``config.json`` keys as they are run; the program's own HF reader
(``models/hf_loader.config_from_hf``) turns them into its DecoderConfig —
the path a user loading that model takes. Whatever is particular to an
architecture is found by a name in the file: its reference module
(``reference``), its published values (``published``), the widths a CPU
rehearsal shrinks (``rehearsal``)."""

import importlib
import json
import os

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: keys of a configuration file that are the benchmark's own and not the
#: published ``config.json``'s: everything else reaches the program's reader
BOOKKEEPING_KEYS = frozenset({
    "source", "reduced", "changed", "assumed", "notes", "stands_for",
    "trainer", "engine", "frontend", "mesh", "reference", "published",
    "rehearsal"})

#: what a reference module has to offer (``benchmark/README.md``)
REFERENCE_CONTRACT = ("Widths", "matmul_params_per_token", "loss",
                      "argmax_gaps")


def load_config(name: str) -> dict:
    path = os.path.join(_HERE, "configs", f"{name}.json")
    with open(path) as fh:
        return json.load(fh)


def load_reference(conf: dict):
    """The module ``benchmark/reference/<name>.py`` the file names under
    ``reference``; without the key, the dense decoder's."""
    name = conf.get("reference", "dense_decoder")
    mod = importlib.import_module(f"benchmark.reference.{name}")
    missing = [a for a in REFERENCE_CONTRACT if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"benchmark/reference/{name}.py lacks "
                             f"{missing} of the reference contract")
    return mod


def load_published(conf: dict) -> dict:
    """The source's ``config.json`` as published: the file
    ``configs/published/<name>.json`` the configuration names under
    ``published``, else the one there with the configuration's ``source``."""
    folder = os.path.join(_HERE, "configs", "published")

    def read(name: str) -> dict:
        with open(os.path.join(folder, name)) as fh:
            return json.load(fh)

    if "published" in conf:
        return read(conf["published"] + ".json")
    for name in sorted(os.listdir(folder)):
        published = read(name)
        if published["source"] == conf["source"]:
            return published
    raise FileNotFoundError(f"no file under benchmark/configs/published/ "
                            f"has the source {conf['source']!r}")


#: ``--rehearse`` only: tiny widths (head size 128 kept so the same kernel
#: family is selected), never a cell. A file's ``rehearsal`` block is
#: merged over these, for the widths this table does not know
REHEARSAL_KEYS = {"hidden_size": 256, "intermediate_size": 512,
                  "num_attention_heads": 2, "num_key_value_heads": 1,
                  "num_hidden_layers": 2, "vocab_size": 512}

#: published key → the attribute of the program's model that has to hold
#: its value. A family's keys for one attribute stand side by side; a file
#: is held to those it has
BUILT_AS = {"hidden_size": "hidden_size",
            "intermediate_size": "intermediate_size",
            "num_attention_heads": "num_heads",
            "num_key_value_heads": "kv_heads",
            "num_hidden_layers": "num_layers",
            "vocab_size": "vocab_size",
            "sliding_window": "sliding_window",
            "rms_norm_eps": "norm_eps",
            "rope_theta": "rope_theta",
            "num_local_experts": "num_experts",
            "num_experts": "num_experts",
            "num_experts_per_tok": "num_experts_per_tok",
            "moe_intermediate_size": "intermediate_size",
            "shared_expert_intermediate_size": "shared_expert_size"}


def published_keys(conf: dict, rehearse: bool = False) -> dict:
    """The file's published keys as they are run: what the program's
    reader and the plain reference are both built from (the file's, not
    the program's model object)."""
    hf = {k: v for k, v in conf.items() if k not in BOOKKEEPING_KEYS}
    if rehearse:
        hf.update(REHEARSAL_KEYS)
        hf.update(conf.get("rehearsal", {}))
    return hf


def build_model(conf: dict, rehearse: bool = False):
    from deepspeed_tpu.models.hf_loader import config_from_hf
    hf = published_keys(conf, rehearse)
    model = config_from_hf(hf)
    # the file is the configuration as run: refuse a reader that drops a
    # width on the way. Where experts have a width of their own, the dense
    # one is of layers the program does not build
    skip = {"intermediate_size"} if "moe_intermediate_size" in hf else set()
    for key, attr in BUILT_AS.items():
        got = getattr(model, attr)
        if key in hf and key not in skip and hf[key] != got:
            raise ValueError(f"config key {key}: file says {hf[key]!r}, "
                             f"the program built {got!r}")
    return model


def prng_key(seed: int):
    """A jax key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
