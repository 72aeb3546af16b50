"""A family's reference is tested on that family's configurations.

``test_reference.py`` holds ``reference/gpt_reference.py`` to
``models/gpt.py``, once for every configuration ``BENCHMARK.json`` has.  A
configuration whose file names another reference (another family: its
``reference`` hook) has nothing for those tests to compare: they are skipped
for it, with the reason, and its own reference is held to its own program
where that family's tests are (``tests/unit/models/test_latent_moe.py`` for
``reference.latent_moe_reference``: bf16 passes, and a left-out term or a
fault in the routed experts does not).  The skip stands only until a
``benchmark`` PR parametrises ``test_reference.py`` by reference (a
``model_config`` PR may not edit that file); it then goes, with this file."""

import json
import os

import pytest

GPT_REFERENCE = "reference.gpt_reference"


def _reference_of(root: str, name: str):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = next((c for c in json.load(f)["configs"]
                      if c["name"] == name), None)
    if entry is None:
        return GPT_REFERENCE
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f).get("reference", GPT_REFERENCE)


def pytest_collection_modifyitems(config, items):
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    for item in items:
        params = getattr(getattr(item, "callspec", None), "params", {})
        if os.path.basename(str(item.fspath)) != "test_reference.py" \
                or os.path.dirname(str(item.fspath)) != here \
                or not isinstance(params.get("name"), str):
            continue
        reference = _reference_of(root, params["name"])
        if reference != GPT_REFERENCE:
            item.add_marker(pytest.mark.skip(
                reason=f"a test of {GPT_REFERENCE}; {params['name']} names "
                       f"{reference}, tested with its own family"))
