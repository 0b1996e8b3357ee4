"""The pod's own namespace.

Template value: a string. A template without the key is in ``default``, the
builders' default. The namespace alone filters and scores nothing, so this
file has no ``State``; a feature whose terms select pods by namespace reads
``pod.features.get("namespace", "default")``.

Refused as ``Unmodelled``: anything but a non-empty string.
"""

from reference import Unmodelled


def parse(value, template: dict) -> str:
    if not isinstance(value, str) or not value:
        raise Unmodelled(f"namespace {value!r}")
    return value
