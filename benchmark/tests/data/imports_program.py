"""A reference no configuration may name: it imports the program."""

import reference
from opentsdb_tpu.query import engine  # noqa: F401

Reference = reference.Reference
