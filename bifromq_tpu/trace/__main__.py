"""``python -m bifromq_tpu.trace [--write]``: the README's span table,
generated from the registry of boundary names."""

import sys

from .names import main

sys.exit(main(sys.argv[1:]))
