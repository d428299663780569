"""The benchmark's door into the program for the causal language-model policy
(``program_sdar.py`` is the block-diffusion policy's, ``program.py``
DreamerV3's).  Importing this module imports the program's model module first,
so a program without it fails here, at once, with an ``ImportError``, before a
driver has built anything.

Everything is reached through what ``ppo.main`` itself calls (``build_agent``,
``build_ppo_optimizer``, ``make_update_fn``: ``program_sdar.LmUpdate`` makes
those calls for either language-model policy) and reads only what the program
exposes."""

from __future__ import annotations

from typing import Any, Dict

import sheeprl_tpu.models.mla_moe as model  # noqa: F401  (the ImportError of a program without the model)
from sheeprl_tpu.models.mla_moe import reference_params  # noqa: F401  (re-exported for the driver)

from chipbench.program_sdar import LmUpdate


class CausalLmUpdate(LmUpdate):
    """``LmUpdate`` for the causal policy: 680 M parameters with their optimizer state are 8.2 GB of
    a chip's 16, so the initial parameters exist once on the device (``initial_params`` lends them to
    the no-gradient pass; ``initial_state`` hands them to the first update, which is donated them)."""

    def initial_params(self):
        """The seed's initial parameters as the first ``initial_state`` will hand them out."""
        if self._first is None:
            self._first = self.fresh_params()[1]
        return self._first

    @property
    def hyper(self) -> Dict[str, Any]:
        return {**super().hyper, "mtp_coef": float(self.policy.aux_coef)}
