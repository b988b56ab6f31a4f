// NA04 fixture: a stats array one field longer than the Python side
// names, and longer than what vtpu_stats writes.
constexpr int NUM_BANKS = 4;
constexpr int kStatsFields = 8;

void vtpu_stats(void* h, uint64_t* out) {
  out[0] = 1;
  out[1] = 2;
  for (int i = 0; i < NUM_BANKS; i++) {
    out[2 + i] = 3;
  }
}
