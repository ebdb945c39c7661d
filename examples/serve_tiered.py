"""Serving example: SkyByte tiered KV vs dense baseline on the same
requests — prints the paper-style serving metrics.

  PYTHONPATH=src python examples/serve_tiered.py
"""
from repro.launch import serve as serve_launcher


def main() -> None:
    for tiering in ("baseline", "skybyte"):
        serve_launcher.main([
            "--arch", "qwen3-1.7b", "--requests", "4",
            "--prompt-len", "24", "--new-tokens", "16",
            "--tiering", tiering,
        ])


if __name__ == "__main__":
    main()
