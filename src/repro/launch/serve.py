"""Serving launcher: run the PecSched mini-cluster over a synthetic request
stream with a reduced model on the default JAX device — the production path
would swap in the full config + production mesh with the dry-run shardings
(`chip_smoke.py` serves the published widths on one TPU).

    PYTHONPATH=src python -m repro.launch.serve --arch mistral_7b --n 24
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced_config
from repro.configs.base import ARCH_IDS
from repro.core.schedulers import POLICY_NAMES
from repro.core.workload import PAPER_SETUPS
from repro.models import init_params
from repro.serving import MiniCluster, ServeRequest


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mistral_7b",
                    choices=ARCH_IDS + list(PAPER_SETUPS))
    ap.add_argument("--policy", default="pecsched", choices=POLICY_NAMES)
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--engines", type=int, default=2)
    ap.add_argument("--sp-degree", type=int, default=0,
                    help="gang-SP degree cap for long prefills "
                         "(0 = host device count; 1 = disable gangs)")
    ap.add_argument("--prefill-target", type=float, default=15.0,
                    help="prefill latency target (s); tight targets make "
                         "longs claim SP groups the backend gang-schedules")
    args = ap.parse_args()

    base = get_config(args.arch)
    if base.family != "dense":
        raise SystemExit("the real-execution engine demo targets the dense "
                         "family (see DESIGN.md); use examples/quickstart.py "
                         "for other families")
    enable_compile_cache()
    cfg = dataclasses.replace(reduced_config(base, layers=4),
                              dtype="float32", sliding_window=0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mc = MiniCluster(cfg, params, n_engines=args.engines, policy=args.policy,
                     max_len=128, enable_sp=args.sp_degree != 1,
                     sp_degree_cap=max(args.sp_degree, 0),
                     target_prefill_s=args.prefill_target)
    rng = np.random.default_rng(0)
    t = 0.0
    for i in range(args.n):
        t += float(rng.exponential(0.05))
        is_long = i % 6 == 5
        slen = 96 if is_long else int(rng.integers(8, 24))
        mc.submit(ServeRequest(rid=i, arrival=t, max_new=4, is_long=is_long,
                               tokens=rng.integers(0, cfg.vocab_size,
                                                   slen).astype(np.int32)))
    mc.run()
    print(mc.metrics())


if __name__ == "__main__":
    main()
