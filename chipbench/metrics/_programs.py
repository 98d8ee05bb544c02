"""Names of the program's jitted entry points as they appear in the
device trace's program (module) line. These follow the function names in
``models/registry.build_serve_step`` and ``serving/scheduler.PrefillFactory``;
a rename there silences the readers that use them."""
SERVE_STEP = r"^jit_serve_step"
PREFILL = r"^jit_prefill"
