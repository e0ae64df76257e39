"""Trace-from-model bridge: registry models -> certified foldable sweeps.

Pipeline (one module per stage, see docs/bridge.md for the contract):

- :mod:`repro.bridge.shapes` — walk a :mod:`repro.configs.registry` model,
  extract every layer's concrete shapes via the :mod:`repro.models` init
  functions (``jax.eval_shape``, no parameter memory), emit
  :class:`LayerOp` records (gemm / attn / scan) with network-level counts;
  ``decode_ops`` lowers one decode step instead (``dgemm`` / ``expert``
  skinny GEMMs at the step's rows, absorbed-MLA ``mla``).
- :mod:`repro.bridge.lower` — lower each op kind to a fixed-shape tile
  program built from ``Assembler.repeat`` deep nests with way-span-padded
  planes, so the outer loops certify exact under :mod:`repro.core.folding`.
- :mod:`repro.bridge.network` — deduplicate by shape signature, register
  one benchmark per unique signature (``net:*`` names, domain
  ``"network"``), and report per-model totals from per-kernel sweeps.

Front door: ``Sweep(network=("granite-8b", ...))`` in :mod:`repro.api`;
``"<model>:decode:batch=B:context=L:zipf=S:seed=N"`` names a decode step.
"""

from repro.bridge.shapes import TOKEN_BLOCK, LayerOp, model_ops
from repro.bridge.lower import (ATTN_TILE, K_CAP, MT, N_CAP, SCAN_STEPS,
                                SCAN_WIDTH_CAP, TILES, build_attn,
                                build_gemm, build_scan, tile_for)
from repro.bridge.network import (LoweredNetwork, NetworkUnit,
                                  lower_network, network_report)

__all__ = [
    "TOKEN_BLOCK", "LayerOp", "model_ops",
    "ATTN_TILE", "K_CAP", "MT", "N_CAP", "SCAN_STEPS", "SCAN_WIDTH_CAP",
    "TILES", "build_attn", "build_gemm", "build_scan", "tile_for",
    "LoweredNetwork", "NetworkUnit", "lower_network", "network_report",
]
