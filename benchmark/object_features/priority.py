"""The pod's priority on the program's pod (``spec.priority``)."""


def apply(builder, value, template: dict):
    return builder.priority(int(value))
