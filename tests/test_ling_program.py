"""Ling as a job runs it: the contract's cases (`tests/lm_contract.py`) at
`tests/spec_ling.py`'s `SPEC` through the trainer and the saver,
`elasticdl train` end to end, `dp_trainer`'s two-step window program
compiled for a described v5e at the cell's widths, and the device scopes
on its op names.  A file of its own, so that `--dist loadfile` gives the
model's heavy compiles a worker beside the one that holds it against its
reference (`tests/test_ling.py`).
"""

from lm_contract import (  # noqa: F401  (the contract's cases, collected here)
    lm, pytest_generate_tests,
    test_rematerialised_layers_run_no_attention_engine_again,
    test_scopes_are_on_the_op_names_and_leave_outputs_bit_equal,
    test_trainer_carries_the_counters_and_checkpoint_restores_the_logits,
    test_two_task_elasticdl_train_end_to_end,
    test_window_program_compiles_and_fits_for_v5e,
)
from spec_ling import SPEC  # noqa: F401  (what `lm` hands the cases)
