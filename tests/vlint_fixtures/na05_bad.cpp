// NA05 fixture: two transports of one bridge; the second parses into
// its stage without an arrival number.
void stamped_loop(Bridge* br, int sock) {
  LocalStage st;
  while (receive(sock)) {
    st.order = arrive(br, 1);
    handle_buffer(br, &st, data, len);
    st.flush(br);
  }
}

void unstamped_loop(Bridge* br, int sock) {
  LocalStage st;
  while (receive(sock)) {
    int rc = handle_ssf(br, &st, data, len);
    st.flush(br);
  }
}
