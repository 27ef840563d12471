"""A throw-away family for the harness's tests: the Mistral family's
equations under another name, behind a configuration file with the shape
of what a `model_config` PR brings (a layer pattern, a count of experts
held here beside the published count, a sliced vocabulary, a nested
group). The pattern, expert and group keys are carried by the file and
computed by nothing: the files' shapes are what the tests hold, and the
harness reads of a configuration only what `dims`, the family's other
functions and the driver ask of it."""
import importlib.util
import pathlib

# this file: <repo>/tests/perfbench/data/foreign/models/foreign.py
_repo = pathlib.Path(__file__).resolve().parents[5]
_spec = importlib.util.spec_from_file_location(
    "foreign_base", _repo / "perfbench" / "models" / "mistral.py")
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
globals().update({k: v for k, v in vars(_base).items()
                  if not k.startswith("__")})
FAMILY_NAME = "foreign"
