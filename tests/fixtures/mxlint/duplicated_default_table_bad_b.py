"""BAD fixture (pair half B): a structurally equal copy in a second
module — the PR 13 DEFAULT_BATCH drift, re-enacted."""

MY_BATCH_TABLE = {
    "lenet": 512,
    "bert": 32,
    "transformer": 8,
    "resnet50": 256,
}
