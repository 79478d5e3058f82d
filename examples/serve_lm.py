"""End-to-end serving driver (deliverable b): batched request serving with
continuous batching, KV caches, and live carbon accounting.

The paper's kind is edge INFERENCE sustainability — this is the e2e driver:
a small LM serves a stream of batched requests; every decode tick is billed
by the CarbonAccountant; the final report answers the paper's question
(operational energy, carbon by grid mix, embodied amortization).

    PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-27b]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgbase
from repro.core import accounting, grid
from repro.launch import runtime
from repro.models import transformer as tf
from repro.serve import ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-27b",
                    help="arch whose SMOKE config is served")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--grid-mix", default="CA")
    args = ap.parse_args()

    arch = cfgbase.get(args.arch)
    if arch.kind != "lm":
        raise SystemExit(f"{args.arch} is {arch.kind}; pick an LM arch")
    cfg = arch.make_smoke()
    params = tf.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32).params

    acct = accounting.CarbonAccountant(accounting.AccountantConfig(
        device=runtime.accountant_device(), n_devices=jax.device_count(),
        grid_mix=args.grid_mix))
    eng = ServeEngine(params, cfg,
                      ServeConfig(max_slots=args.slots, max_len=256,
                                  cache_dtype=jnp.float32),
                      accountant=acct)

    rng = np.random.default_rng(0)
    print(f"serving {args.requests} requests on {args.arch} (smoke config), "
          f"{args.slots} slots, continuous batching:")
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=int(rng.integers(4, 16)))
        eng.submit(prompt, max_tokens=args.max_tokens)
    done = eng.run_until_drained()
    for r in done[:6]:
        print(f"  req {r.uid:2d}: {len(r.prompt):2d} prompt toks -> "
              f"{len(r.generated)} generated")
    print(f"  ... {len(done)} requests completed")

    s = eng.summary()
    print(f"\nserve metrics (live path): {s['ticks']} ticks, "
          f"{s['decode_tokens']:.0f} decode tokens at "
          f"{s['decode_tokens_per_s']:.1f} tok/s "
          f"(+ {s['prefill_tokens']:.0f} prefill tokens)")

    rep = acct.report()
    print("\ncarbon report:")
    print(f"  decode ticks: {rep['steps']}, tokens: {rep['tokens']:.0f}")
    if rep.get("j_per_token") is not None:
        print(f"  J/token (live): {rep['j_per_token']:.3f}")
    print(f"  operational: {rep['operational_j']:.1f} J = "
          f"{rep['operational_gco2']:.4f} gCO2eq ({args.grid_mix} grid)")
    print(f"  tokens/J: {rep['tokens_per_j']:.2f}")
    print(f"  fleet embodied budget: {rep['embodied_j']/1e6:.0f} MJ "
          f"({rep['embodied_gco2']/1e3:.1f} kgCO2eq)")
    print(f"  lifecycle amortized so far: {rep['amortized_fraction']:.2e}")
    print("\n(the production decode shapes are proven by "
          "`python -m repro.launch.dryrun --arch "
          f"{args.arch} --shape decode_32k`)")


if __name__ == "__main__":
    main()
