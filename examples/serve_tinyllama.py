"""End-to-end serving driver: batched requests through the engine
(continuous-batching-lite) on TinyLlama-42M — the paper's decoder workload.

    PYTHONPATH=src python examples/serve_tinyllama.py [--full]

``--full`` uses the real 42M config (slower on CPU); default is the reduced
smoke model.  Demonstrates chunked prefill into paged KV -> fused batch
decode, greedy sampling, TTFT/TPOT reporting — the autoregressive mode the
paper accelerates 26.1x.
"""
import sys

from repro.launch.serve import main as serve_main


def main():
    args = ["--arch", "tinyllama-42m", "--requests", "12", "--slots", "4",
            "--seq-budget", "128", "--prompt-len", "24", "--max-new", "12"]
    if "--full" not in sys.argv:
        args.append("--smoke")
    return serve_main(args)


if __name__ == "__main__":
    sys.exit(main())
