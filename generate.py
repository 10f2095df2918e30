"""Generate text from a trained checkpoint (recurrent O(1) decode).

Ships the reference's ``LMHeadModel.generate``/``top_k_sampling``
capability (/root/reference/model.py:49-95) as a standalone CLI — but
with parallel prefill + carried recurrent state in one jit instead of
the reference's full-prefix re-forward per token (SURVEY.md §3.3).

Examples:
  python generate.py --checkpoint ckpt --preset mamba2-280m \
      --prompt "Hello, I'm a language model,"
  python generate.py --hf-path /path/to/state-spaces-dir \
      --prompt-ids "15496,11,314" --max-new-tokens 64
"""

from __future__ import annotations

import argparse
import os


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--checkpoint", help="Orbax checkpoint dir (train.py)")
    src.add_argument("--hf-path",
                     help="local HF dir (config.json + pytorch_model.bin) "
                          "or reference-style .pt")
    p.add_argument("--preset", default="mamba2-280m",
                   help="model preset (ignored for --hf-path dirs, which "
                        "carry their own config.json)")
    p.add_argument("--prompt", default=None,
                   help="text (tokenized by the vendored GPT-2 BPE from "
                        "$GPT2_BPE_DIR / ./gpt2_bpe, tiktoken fallback)")
    p.add_argument("--prompt-ids", default=None,
                   help="comma-separated token ids (no tokenizer needed)")
    p.add_argument("--num-return", type=int, default=4)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--top-k", type=int, default=50)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=42)  # reference train.py:174
    return p.parse_args()


def main():
    args = parse_args()

    from mamba_distributed_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()

    import jax
    import jax.numpy as jnp

    # --- prompt ---
    decode_fn = None
    if args.prompt_ids is not None:
        ids = [int(t) for t in args.prompt_ids.split(",")]
    elif args.prompt is not None:
        from mamba_distributed_tpu.data.gpt2_bpe import load_encoder

        try:
            # vendored zero-egress BPE (local gpt2_bpe/), tiktoken fallback
            encode, decode_fn = load_encoder()
        except FileNotFoundError as e:
            raise SystemExit(f"--prompt: {e}\nOr pass --prompt-ids instead.")
        ids = encode(args.prompt)
    else:
        raise SystemExit("pass --prompt or --prompt-ids")

    # --- params + config (same routing as eval.py: .pt files go through
    # the HF/reference-style importer, directories through Orbax) ---
    from eval import load_custom, load_hf

    if args.hf_path:
        if os.path.isdir(args.hf_path):
            params, cfg_model = load_hf(args.hf_path)
        else:
            params, cfg_model = load_custom(args.hf_path, args.preset)
    else:
        params, cfg_model = load_custom(args.checkpoint, args.preset)

    from mamba_distributed_tpu.inference import generate

    prompt = jnp.tile(jnp.asarray(ids, jnp.int32)[None, :],
                      (args.num_return, 1))
    out = generate(
        params, cfg_model, prompt, jax.random.PRNGKey(args.seed),
        max_new_tokens=args.max_new_tokens, top_k=args.top_k,
        temperature=args.temperature,
    )
    import numpy as np

    for row in np.asarray(out):
        text = decode_fn(row.tolist()) if decode_fn else f"tokens {row.tolist()}"
        print(f"> {text}")


if __name__ == "__main__":
    main()
