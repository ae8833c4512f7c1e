"""The one place the benchmark reaches into the program under test.

It builds the program's model configuration from a configuration file, the
engine the launcher builds (`repro.launch.serve.build_engine`), and reads
the engine's requests and counters. The program lives under ``src/`` of the
checkout.
"""

from __future__ import annotations

import dataclasses
import sys

from harness.spec import ROOT

# Published config.json keys and the program's names for them: the sizes a
# configuration file states are checked against the config the program runs.
CONFIG_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def import_program(root=ROOT) -> None:
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: its ``program``
    entry names the architecture and the settings to change, and every size
    the file states must then agree with what the program will run."""
    from repro.configs import get_config
    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog.get("set", {}))
    stated = [(k, f, config[k]) for k, f in CONFIG_KEYS.items() if k in config]
    stated += [(k, k, v) for k, v in config["serving"].items() if hasattr(cfg, k)]
    for key, field, value in stated:
        if getattr(cfg, field) != value:
            raise ValueError(f"{prog['arch']}: {key} is {value} in the "
                             f"configuration file but {getattr(cfg, field)} "
                             "in the program")
    return cfg


def param_shapes(cfg):
    import jax

    from repro.models import get_model
    return jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))


def build_engine(cfg, params, engine: dict, serving: dict):
    """The serving engine as the launcher builds it: paged pool, prefix
    cache, chunked prefill, the decode path the configuration states."""
    from repro.launch.serve import build_engine as launcher_build
    return launcher_build(
        cfg, params, slots=engine["slots"], max_seq=engine["max_seq"],
        paged=True, block_size=serving["block_size"],
        num_blocks=engine["num_blocks"], prefix_cache=True,
        prefill_chunk=engine["prefill_chunk"],
        fused_decode=serving["fused_decode"])


def make_request(item, submitted: float):
    from repro.runtime.serve import Request
    return Request(rid=item.rid, prompt=item.prompt.copy(),
                   max_new_tokens=item.max_new, submitted=submitted)


def resend(req, rid: int, submitted: float):
    """The same prompt and length again, as a new request."""
    from repro.runtime.serve import Request
    return Request(rid=rid, prompt=req.prompt.copy(),
                   max_new_tokens=req.max_new_tokens, submitted=submitted)


def busy(engine) -> bool:
    """Work queued, in flight or decoding."""
    return bool(engine._queue or engine._active or engine._inflight is not None)


def counters(engine) -> dict:
    """The engine's counters, as numbers (a snapshot)."""
    return dataclasses.asdict(engine.stats)
