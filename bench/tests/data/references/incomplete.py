"""A configuration's module that brings weights but no reference."""
from benchlib.model import make_params  # noqa: F401
