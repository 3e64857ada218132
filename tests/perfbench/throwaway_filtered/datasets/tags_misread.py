"""The tags dataset with its reading of ONE filter deliberately wrong: the
filter of pool query 0 is read as asking for the next tag. Rows, properties
and the filters sent are the tags dataset's, so the server answers query 0
rightly and the run must come out not `correct`: rows outside the filter as
this dataset reads it (`disallowed_rows`), and a ground truth the replies
miss. Test only."""

import importlib.util
import json
import os

_spec = importlib.util.spec_from_file_location(
    "perfbench_throwaway_tags",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tags.py"))
tags = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tags)

properties, filter_plan = tags.properties, tags.filter_plan


def _shifted(where: dict) -> dict:
    if "operands" in where:
        return dict(where, operands=[_shifted(w) for w in where["operands"]])
    return dict(where, valueInt=where["valueInt"] + 1)


def allowed(cfg, wheres, rows):
    first = json.dumps(filter_plan(cfg, None)[0], sort_keys=True)
    return tags.allowed(cfg, [
        _shifted(w) if json.dumps(w, sort_keys=True) == first else w
        for w in wheres], rows)
