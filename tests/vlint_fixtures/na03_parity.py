"""NA03 fixture companion: the Python-side frame layout."""

VERSION_BYTE = 0x00
LENGTH_BYTES = 4
LENGTH_LITTLE_ENDIAN = 1
MAX_FRAME_LENGTH = 16 * 1024 * 1024
