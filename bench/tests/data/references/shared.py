"""A configuration's own reference that is the shared one, re-exported."""
from benchlib.reference import logits_in_blocks  # noqa: F401
