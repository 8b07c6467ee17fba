"""Phi-3 (``model_type`` "phi3"; huggingface.co/microsoft/Phi-3-mini-4k-
instruct): the dense decoder of ``arch/_dense.py``."""

from arch._dense import (check_traffic, logits_at, loss,  # noqa: F401
                         matmul_params_per_token, program, shapes)
