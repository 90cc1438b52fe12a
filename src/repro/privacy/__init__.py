"""Privacy subsystem: client-side DP-SGD, an RDP accountant, and
simulated secure aggregation — the paper's research-direction axes
(SSVI; FedLLM survey arXiv:2503.12016) as first-class scenario knobs.

Configured by ``configs/base.PrivacyConfig`` (``FedConfig.privacy``);
wired through the one round driver (core/round_program.py) uniformly
over the three frameworks, every execution backend and both
aggregation schedules.  Per-framework threat surfaces:

==========  =========================  ================================
framework   private payload            mechanism
==========  =========================  ================================
FedLLM      LoRA param upload (a3)     per-example grad clip (DP-SGD)
                                       + Gaussian noise on the params
                                       + secure-agg masks on the upload
KD-FedLLM   public-set logits (b3)     per-example grad clip in b1 +
                                       row-clipped noisy logits (before
                                       top-k/int-quant compression) +
                                       secure-agg masks on the upload
Split       smashed activations (c2)   per-token-row clip + Gaussian
            + client-half LoRA (cc1)   noise on every boundary
                                       transfer; secure-agg masks on
                                       the adapter upload
==========  =========================  ================================
"""
from repro.privacy.accountant import GaussianAccountant  # noqa: F401
from repro.privacy.dp import (clipped_grad_mean, noise_key,  # noqa: F401
                              privatize_logits, privatize_rows,
                              privatize_tree)
from repro.privacy.secure_agg import SecureAggSession  # noqa: F401
