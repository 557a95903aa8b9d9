"""The share of an EC GET's server residence that no stage names: what
`ec.get.handler` encloses and none of the exclusive stages covers, over
`ec.get`. The hop to the second plane (`ec.get` less `ec.get.handler`)
counts as named."""
from stage_counters import OPTIONAL, REQUIRED, seconds, total


def read(run: dict) -> float | None:
    whole, handler = seconds(run, "ec.get"), seconds(run, "ec.get.handler")
    named = total(run, REQUIRED, OPTIONAL)
    if not whole or handler is None or named is None:
        return None
    return 100.0 * (handler - named) / whole
