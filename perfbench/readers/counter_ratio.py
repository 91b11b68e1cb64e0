"""Window delta of one labelled series of a process-global counter over
another's: ``numerator`` / ``denominator``, each ``{"family": name,
"labels": "value,value"}`` (the label values of the series, comma-joined
in the family's label order; "" for a family without labels). None where
the program has no such family or the denominator did not move."""


def delta(context, family: str, labels: str) -> float | None:
    """None if the family is not in the run's last snapshot."""
    after = context.ran["after"]["registry"].get(family)
    if after is None:
        return None
    before = context.ran["before"]["registry"].get(family, {})
    return float(after["values"].get(labels, 0.0)) \
        - float(before.get("values", {}).get(labels, 0.0))


def read(context, numerator, denominator):
    top = delta(context, numerator["family"], numerator["labels"])
    bottom = delta(context, denominator["family"], denominator["labels"])
    if top is None or bottom is None or bottom <= 0:
        return None
    return top / bottom
