import os

# Tests run on the host's real device(s); the 512-device override belongs to
# launch/dryrun.py ONLY.  A couple of distribution tests spawn subprocesses
# that set their own XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
