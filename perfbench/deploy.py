"""A deployment from its configuration file: the class model of the
stream, σ_ini, the program's ``FIGMNConfig`` and the starting pool handed
to the program through ``StreamRuntime.import_pool``."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from perfbench import figmn_ref, pool, streams


@dataclasses.dataclass
class Deployment:
    config: Dict[str, Any]
    seed: int
    model: streams.ClassModel
    sigma: np.ndarray

    @property
    def dim(self) -> int:
        return self.model.dim

    @property
    def features(self) -> int:
        return self.model.features

    @property
    def classes(self) -> int:
        return self.model.classes

    @property
    def kmax(self) -> int:
        return int(self.config["kmax"])

    @property
    def threshold(self) -> float:
        return figmn_ref.novelty_threshold(self.dim, self.config["beta"])

    def figmn_config(self):
        """The program's configuration of this deployment."""
        from repro.core.types import FIGMNConfig
        c = self.config
        return FIGMNConfig(
            kmax=self.kmax, dim=self.dim, beta=c["beta"], delta=c["delta"],
            vmin=c["vmin"], spmin=c["spmin"], dtype_str=c["dtype"],
            update_mode=c["update_mode"], sigma_ini=jnp.asarray(self.sigma))

    def start_pool(self, dtype: str = "float32"):
        """The ingest cells' starting pool (dict of device arrays), from
        the seed."""
        return pool.full_pool(self.model, self.seed, self.kmax, self.sigma,
                              dtype)

    def served_pool(self, shape: Dict[str, Any], dtype: str = "float32"):
        """The read cell's published snapshot, from the seed."""
        return pool.served_pool(self.model, self.seed, self.kmax,
                                self.sigma, shape, dtype)

    def start_means(self) -> np.ndarray:
        return pool.means(self.model, self.seed, self.kmax)


#: the stream generators a configuration may name
GENERATORS = ("gaussian_classes",)


def deployment(config: Dict[str, Any], seed: int) -> Deployment:
    c = config
    if c["stream"]["generator"] not in GENERATORS:
        raise ValueError(f"unknown stream generator "
                         f"{c['stream']['generator']!r}; known: "
                         f"{GENERATORS}")
    model = streams.class_model(seed, c["features"], c["classes"],
                                c["stream"]["sep"])
    if model.dim != c["dim"]:
        raise ValueError(f"features + classes = {model.dim} != dim "
                         f"{c['dim']}")
    sigma = streams.sigma_ini(model, seed, c["delta"])
    return Deployment(config, seed, model, sigma)


def to_state(p):
    """A pool dict as the program's ``FIGMNState``."""
    from repro.core.types import FIGMNState
    k = p["mu"].shape[0]
    return FIGMNState(mu=p["mu"], lam=p["lam"], logdet=p["logdet"],
                      sp=p["sp"], v=p["v"], active=p["active"],
                      n_created=jnp.asarray(k, jnp.int32))


def from_state(s) -> Dict[str, Any]:
    return {"mu": s.mu, "lam": s.lam, "logdet": s.logdet, "sp": s.sp,
            "v": s.v, "active": s.active}
