"""The pod's own namespace on the program's pod."""


def apply(builder, value, template: dict):
    return builder.namespace(value)
