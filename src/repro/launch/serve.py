"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Prefill + greedy decode with the batched engine; ``--session`` persists the
decode state into a (combined) storage window so generation can resume
after a restart.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core import Communicator
from repro.models import init_cache_specs, init_params, param_specs
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import Engine, SessionStore


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--session", default=None,
                    help="path for a window-backed resumable session")
    ap.add_argument("--session-factor", default="0.5")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(param_specs(cfg), jax.random.PRNGKey(0))
    enc_len = 16 if cfg.is_encdec else 0
    session = None
    if args.session:
        session = SessionStore(
            Communicator(1), args.session,
            init_cache_specs(cfg, args.batch, args.max_len, enc_len),
            factor=args.session_factor)
    eng = Engine(cfg, params, batch=args.batch, max_len=args.max_len,
                 enc_len=enc_len, session=session)
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (args.batch, args.prompt_len), 0,
                              cfg.vocab).astype("int32")
    batch = {"inputs": toks}
    if cfg.frontend == "vlm_stub":
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.img_tokens, cfg.d_model),
            "bfloat16")
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(3), (args.batch, enc_len, cfg.d_model),
            "bfloat16")
    out = eng.generate(batch, args.steps)
    print("generated token ids (batch 0):", out[0].tolist())
    if session:
        print("session flushed:", eng.save_session(), "bytes")
        session.free()


if __name__ == "__main__":
    main()
