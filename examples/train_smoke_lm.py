"""End-to-end training driver example: trains the ~100M-param smoke variant
of deepseek-coder for a few hundred steps on the deterministic synthetic
pipeline, with checkpoint/resume — the 'train a small model end to end'
deliverable. (The full-size configs use the same driver via launch/train.py
on a real pod.)

    PYTHONPATH=src python examples/train_smoke_lm.py [--steps 300]
"""
import argparse

from repro.launch.train import main as train_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_smoke")
    args = ap.parse_args()

    losses = train_main([
        "--arch", "deepseek_coder_33b", "--smoke",
        "--steps", str(args.steps),
        "--batch", "8", "--seq", "128",
        "--lr", "3e-3",
        "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50", "--resume",
    ])
    assert losses[-1] < losses[0], "loss must decrease"
    print(f"\nloss {losses[0]:.3f} -> {losses[-1]:.3f} over "
          f"{len(losses)} steps; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
