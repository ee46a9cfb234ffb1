"""Batched LM serving demo: greedy decode with KV cache, optionally with
the paper's quantisation applied at LM scale (int8 weights + int8 KV).

Run:  PYTHONPATH=src python examples/serve_lm.py
      PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-7b
      PYTHONPATH=src python examples/serve_lm.py --quant w8 --kv-int8
"""
from repro.compile_cache import use_compile_cache
from repro.launch.serve import main
use_compile_cache()
main()
