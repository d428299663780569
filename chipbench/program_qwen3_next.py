"""The benchmark's door into the program for the Qwen3-Next policy (linear
attention with a recurrent state and gated full attention mixed;
``program_joyai.py`` is latent attention's).  Importing this module imports the
program's model module first, so a program without it fails here, at once,
with an ``ImportError``, before a driver has built anything.  The update is
built as ``ppo.main`` builds it, by the causal policies' door
(``program_joyai.CausalLmUpdate``)."""

from __future__ import annotations

import sheeprl_tpu.models.qwen3_next as model  # noqa: F401  (the ImportError of a program without the model)
from sheeprl_tpu.models.qwen3_next import reference_params  # noqa: F401  (re-exported for the driver)

from chipbench.program_joyai import CausalLmUpdate  # noqa: F401  (the update's door, re-exported)
