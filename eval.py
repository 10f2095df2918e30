"""HellaSwag evaluation CLI.

Mirror of the reference's ``python eval.py -m custom|hugging_face ...``
(/root/reference/eval.py:186-200), with its bugs fixed: the reversed
``Enum`` bases that crashed at import and the ``hugging_face`` branch that
never constructed a model (SURVEY.md §3.4) both work here.

  python eval.py -m custom --checkpoint <orbax-dir> --preset mamba2-280m
  python eval.py -m custom --checkpoint model.pt --preset mamba2-280m
  python eval.py -m hugging_face --hf-path <local HF dir>

Needs a GPT-2 BPE tokenizer and a local hellaswag_val.jsonl (a download
the reference does on the fly).  Tokenization is zero-egress: the BPE
algorithm is vendored (mamba_distributed_tpu/data/gpt2_bpe.py) and loads
local encoder.json/vocab.bpe (or HF vocab.json/merges.txt) from
--bpe-dir / $GPT2_BPE_DIR / ./gpt2_bpe, with tiktoken as a fallback.
"""

from __future__ import annotations

import argparse
import enum


class ModelType(str, enum.Enum):  # reference eval.py:22 had the bases reversed
    CUSTOM = "custom"
    HF = "hugging_face"


def get_encoder(bpe_dir: str | None = None):
    from mamba_distributed_tpu.data.gpt2_bpe import load_encoder

    try:
        # vendored zero-egress BPE (local gpt2_bpe/ files), tiktoken fallback
        encode, _ = load_encoder(bpe_dir)
        return encode
    except FileNotFoundError as e:
        raise SystemExit(
            f"GPT-2 tokenizer unavailable: {e}\n(Or inject your own encode "
            "via the library API mamba_distributed_tpu.eval.evaluate_hellaswag.)"
        )


def load_custom(checkpoint: str, preset: str):
    from mamba_distributed_tpu.config import get_preset

    cfg = get_preset(preset).model
    if checkpoint.endswith(".pt"):
        from mamba_distributed_tpu.models.hf import load_hf_checkpoint

        params, cfg = load_hf_checkpoint(checkpoint, cfg)
    else:
        from mamba_distributed_tpu.training.checkpoint import restore_params_only

        params = restore_params_only(checkpoint)
        got = tuple(params["embedding"].shape)
        want = (cfg.vocab_size_padded, cfg.d_model)
        if got != want:
            raise SystemExit(
                f"checkpoint/preset mismatch: embedding {got} in "
                f"{checkpoint!r} but --preset {preset!r} expects {want} — "
                f"pass the preset the checkpoint was trained with"
            )
    return params, cfg


def load_hf(path: str):
    from mamba_distributed_tpu.models.hf import load_hf_checkpoint

    return load_hf_checkpoint(path)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("-m", "--model_type", default="custom",
                   choices=[m.value for m in ModelType])
    p.add_argument("--checkpoint", default="log/checkpoint")
    p.add_argument("--preset", default="mamba2-280m")
    p.add_argument("-v", "--hf-path", default=None,
                   help="local HF directory (config.json + pytorch_model.bin)")
    p.add_argument("--data-file", default="hellaswag/hellaswag_val.jsonl")
    p.add_argument("--limit", type=int, default=2000)
    p.add_argument("--example-batch", type=int, default=8,
                   help="examples packed per device call (scores unchanged)")
    p.add_argument("--log-file", default="log/hellaswag_eval.txt")
    p.add_argument("--bpe-dir", default=None,
                   help="dir with GPT-2 encoder.json/vocab.bpe (or HF "
                   "vocab.json/merges.txt); default $GPT2_BPE_DIR or "
                   "./gpt2_bpe, falling back to tiktoken")
    args = p.parse_args()

    from mamba_distributed_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()

    from mamba_distributed_tpu.eval import evaluate_hellaswag, iterate_examples
    from mamba_distributed_tpu.models import lm_forward

    if args.model_type == ModelType.HF.value:
        assert args.hf_path, "--hf-path required for hugging_face"
        params, cfg = load_hf(args.hf_path)
    else:
        params, cfg = load_custom(args.checkpoint, args.preset)

    result = evaluate_hellaswag(
        lambda tokens: lm_forward(params, cfg, tokens),
        iterate_examples(args.data_file),
        get_encoder(args.bpe_dir),
        limit=args.limit,
        log_path=args.log_file,
        verbose=True,
        example_batch=args.example_batch,
    )
    print(result)


if __name__ == "__main__":
    main()
