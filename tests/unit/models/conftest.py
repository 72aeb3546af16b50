"""Collection order of the families' conformance suite."""


def pytest_collection_modifyitems(items):
    """``test_family_conformance.py``'s cases family by family (a family's
    probes in the file's order) where pytest collects them probe by probe:
    the scheduler hands a worker runs of consecutive cases, and a family's
    loud model, its gateway's programs and its compiled reference are made
    once a process that meets it.  Ids and counts are as collected."""
    at = [i for i, item in enumerate(items)
          if item.module.__name__.endswith("test_family_conformance")]
    first_seen = {}
    for i in at:
        first_seen.setdefault(items[i].callspec.params["name"],
                              len(first_seen))
    moved = sorted((items[i] for i in at),
                   key=lambda item: first_seen[item.callspec.params["name"]])
    for i, item in zip(at, moved):
        items[i] = item
