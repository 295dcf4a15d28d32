"""Storage engine error classes."""


class StorageError(Exception):
    pass


class NotFoundError(StorageError):
    pass


class Corruption(StorageError):
    pass


class InvalidArgument(StorageError):
    pass
