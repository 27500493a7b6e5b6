"""Build the system under test for one configuration file.

The benchmark makes the encoder's weights from the seed (through the
configuration's plain reference, in one jitted call on the device) and
hands them to the program's ``EncoderStage``; the engine then serves
through the configuration's backend.  Nothing here changes what the
program computes.
"""

from __future__ import annotations

import dataclasses

import jax

from repro.configs.base import get_config
from repro.core import SolveConfig
from repro.embeddings.serving import EncoderStage
from repro.farm import CobiFarm
from repro.obs import Observability
from repro.serving import SummarizationEngine

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
              "vocab_size", "max_seq_len", "param_dtype", "norm_eps",
              "rope_theta", "act", "gated_mlp")


def model_config(enc: dict):
    """The program's ``ModelConfig`` holding the file's encoder sizes."""
    cfg = get_config(enc["arch"])
    return cfg.replace(**{k: enc[k] for k in MODEL_KEYS})


def weight_key(seed: int):
    return jax.random.fold_in(jax.random.key(seed % 2**32), 1)


def make_weights(config: dict, reference, seed: int):
    enc = config["encoder"]
    return reference.make_weights(enc, weight_key(seed), enc["param_dtype"])


def solve_config(config: dict) -> SolveConfig:
    return SolveConfig(**config["solve"])


@dataclasses.dataclass
class System:
    engine: SummarizationEngine
    stage: EncoderStage
    farm: object  # CobiFarm or None
    obs: Observability


def build(config: dict, weights, *, seed: int, lam: float,
          tracing: bool) -> System:
    """Encoder stage -> engine -> backend, as the configuration states."""
    enc = config["encoder"]
    cfg = model_config(enc)
    stage = EncoderStage(cfg, weights, max_len=enc["max_seq_len"])
    be = config["backend"]
    obs = Observability(tracing=tracing, capacity=1 << 20)
    kw: dict = {}
    farm = None
    if be["kind"] == "cobi_farm":
        farm = CobiFarm(be["n_chips"], policy=be["policy"],
                        validate=be["validate"])
        kw["farm"] = farm
    elif be["kind"] == "mcmc_bank":
        kw["pool_workers"] = be["workers"]
    else:
        raise ValueError(f"unknown backend kind {be['kind']!r}")
    engine = SummarizationEngine(
        solve_config(config), encoder=stage, lam=lam,
        score_against_exact=False, seed=seed % 2**32, obs=obs, **kw)
    return System(engine, stage, farm, obs)
