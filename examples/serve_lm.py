"""Serve a small model with batched prefill+decode, then plan request
replication from the measured service times (paper §VII methodology).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
from repro.compile_cache import enable_compile_cache
from repro.launch import serve


def main():
    serve.main([
        "--arch", "qwen2-1.5b", "--smoke",
        "--requests", "6", "--prompt-len", "24", "--gen", "8",
        "--workers", "12",
    ])


if __name__ == "__main__":
    enable_compile_cache()
    main()
