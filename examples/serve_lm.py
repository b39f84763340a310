"""End-to-end serving driver (the paper's kind is inference acceleration):
serve a small LM with batched requests — prefill + token-by-token decode
against a persistent KV cache, with optional int8 weight compression (the
HLS4PC technique applied to the LM path).

    PYTHONPATH=src python examples/serve_lm.py --arch tinyllama-1.1b \
        --batch 4 --prompt-len 64 --gen 32 [--w8]

Uses the reduced smoke config on CPU; on TPU the same entry points run
the full config (--full).
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.core.quant import QuantConfig, quantize_tree
from repro.launch.profile import configure_compile_cache
from repro.models.api import get_model
from repro.serve.engine import Engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--w8", action="store_true",
                    help="deploy int8 weights (W8A16 decode)")
    ap.add_argument("--full", action="store_true",
                    help="full published config (TPU-scale)")
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = (get_config if args.full else get_smoke_config)(args.arch)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    if args.w8:
        qcfg = QuantConfig(w_bits=8, a_bits=16, backend="int8_ref")
        params = quantize_tree(params, qcfg)
        cfg = cfg.replace(quant=qcfg)
        api = get_model(cfg)
        print("deployed int8 weights (W8A16)")

    eng = Engine(api, params, max_len=args.prompt_len + args.gen + 1,
                 batch_size=args.batch, temperature=args.temperature)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    out = eng.generate({"tokens": prompts}, args.gen)
    st = out["stats"]
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill {st.prefill_s*1e3:.0f} ms | decode "
          f"{st.decode_s*1e3:.0f} ms | {st.decode_tok_per_s:.1f} tok/s")
    print("first request ids:", out["ids"][0][:16].tolist())


if __name__ == "__main__":
    main()
