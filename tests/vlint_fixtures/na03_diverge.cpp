// NA03 fixture: a frame layout whose maximum diverges from the Python
// side's, and which leaves the byte order out.
constexpr int kSsfFrameVersion = 0;
constexpr int kSsfFrameLengthBytes = 4;
constexpr int kSsfMaxFrameLength = 8 * 1024 * 1024;
