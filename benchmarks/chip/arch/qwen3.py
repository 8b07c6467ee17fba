"""Qwen3 (``model_type`` "qwen3"; huggingface.co/Qwen/Qwen3-1.7B): the
dense decoder of ``arch/_dense.py``."""

from arch._dense import (check_traffic, logits_at, loss,  # noqa: F401
                         matmul_params_per_token, program, shapes)
