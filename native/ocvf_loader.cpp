// Native host-side image loader for opencv_facerecognizer_tpu.
//
// The reference's host decode path was native C++ (OpenCV's imread/resize —
// SURVEY.md §2.2 "cv2.resize, cv2.cvtColor, image decode"). This is the
// rebuild's native equivalent for the formats the classic face datasets
// actually use (ORL/AT&T and Yale-B ship PGM; PPM/BMP cover the other
// uncompressed cases): decode -> grayscale luminance -> fused bilinear
// resize straight into a caller-provided float32 buffer, so read_images can
// pack a training batch without any intermediate Python objects. JPEG/PNG
// fall back to PIL in utils/native.py (libjpeg/libpng linkage isn't worth
// it when the fallback already covers them).
//
// It also decodes the connectors' wire form of a frame (base64 text,
// runtime/connector.py): ocvf_b64_decode, called through ctypes with the
// interpreter's lock released.
//
// Build: g++ -O3 -shared -fPIC -o libocvf_loader.so ocvf_loader.cpp
// (utils/native.py does this on demand and caches the .so).

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <vector>

namespace {

constexpr int kErrRead = -1;
constexpr int kErrFormat = -2;
constexpr int kErrBounds = -3;

struct GrayImage {
  int h = 0;
  int w = 0;
  std::vector<float> px;  // luminance, [0, 255]
};

// ---- PNM (P2/P3/P5/P6) ----

bool pnm_token(const uint8_t* d, int64_t n, int64_t& pos, long& out) {
  // Skip whitespace and '#' comments, then parse one non-negative integer.
  while (pos < n) {
    uint8_t c = d[pos];
    if (c == '#') {
      while (pos < n && d[pos] != '\n') pos++;
    } else if (c == ' ' || c == '\t' || c == '\r' || c == '\n') {
      pos++;
    } else {
      break;
    }
  }
  if (pos >= n || d[pos] < '0' || d[pos] > '9') return false;
  long v = 0;
  while (pos < n && d[pos] >= '0' && d[pos] <= '9') {
    v = v * 10 + (d[pos] - '0');
    pos++;
  }
  out = v;
  return true;
}

int decode_pnm(const uint8_t* d, int64_t n, GrayImage& img) {
  if (n < 2 || d[0] != 'P') return kErrFormat;
  int kind = d[1] - '0';
  if (kind != 2 && kind != 3 && kind != 5 && kind != 6) return kErrFormat;
  bool color = (kind == 3 || kind == 6);
  bool ascii = (kind == 2 || kind == 3);
  int64_t pos = 2;
  long w, h, maxval;
  if (!pnm_token(d, n, pos, w) || !pnm_token(d, n, pos, h) ||
      !pnm_token(d, n, pos, maxval))
    return kErrFormat;
  if (w <= 0 || h <= 0 || w > 1 << 16 || h > 1 << 16 || maxval <= 0 ||
      maxval > 65535)
    return kErrFormat;
  double scale = 255.0 / (double)maxval;
  int64_t count = (int64_t)h * w * (color ? 3 : 1);
  // Bounds-check BEFORE allocating h*w pixels: a crafted header like
  // "P5 60000 60000" over a 1-byte body must fail here, not in a 14 GB
  // px.resize (std::bad_alloc aborts the process across the ctypes
  // boundary). ASCII needs >= 2 bytes (digit + separator) per value.
  int64_t min_body = ascii ? 2 * count - 1 : count * (maxval > 255 ? 2 : 1);
  if (pos + min_body > n) return kErrBounds;
  img.h = (int)h;
  img.w = (int)w;
  img.px.resize((size_t)h * w);

  if (ascii) {
    std::vector<long> vals((size_t)count);
    for (int64_t i = 0; i < count; i++) {
      if (!pnm_token(d, n, pos, vals[(size_t)i])) return kErrBounds;
    }
    for (int64_t i = 0; i < (int64_t)h * w; i++) {
      double v = color ? 0.299 * vals[(size_t)(3 * i)] +
                             0.587 * vals[(size_t)(3 * i + 1)] +
                             0.114 * vals[(size_t)(3 * i + 2)]
                       : (double)vals[(size_t)i];
      img.px[(size_t)i] = (float)(v * scale);
    }
    return 0;
  }

  pos += 1;  // exactly one whitespace byte after maxval in binary PNM
  int bytes_per = maxval > 255 ? 2 : 1;
  if (pos + count * bytes_per > n) return kErrBounds;
  const uint8_t* p = d + pos;
  for (int64_t i = 0; i < (int64_t)h * w; i++) {
    double c0, c1, c2;
    if (bytes_per == 1) {
      if (color) {
        c0 = p[3 * i]; c1 = p[3 * i + 1]; c2 = p[3 * i + 2];
      } else {
        c0 = c1 = c2 = p[i];
      }
    } else {  // 16-bit PNM is big-endian
      auto rd = [&](int64_t j) { return (double)((p[2 * j] << 8) | p[2 * j + 1]); };
      if (color) {
        c0 = rd(3 * i); c1 = rd(3 * i + 1); c2 = rd(3 * i + 2);
      } else {
        c0 = c1 = c2 = rd(i);
      }
    }
    double v = color ? 0.299 * c0 + 0.587 * c1 + 0.114 * c2 : c0;
    img.px[(size_t)i] = (float)(v * scale);
  }
  return 0;
}

// ---- BMP (uncompressed 8/24/32-bit) ----

uint32_t le32(const uint8_t* p) {
  return (uint32_t)p[0] | ((uint32_t)p[1] << 8) | ((uint32_t)p[2] << 16) |
         ((uint32_t)p[3] << 24);
}
uint16_t le16(const uint8_t* p) { return (uint16_t)(p[0] | (p[1] << 8)); }

int decode_bmp(const uint8_t* d, int64_t n, GrayImage& img) {
  if (n < 54 || d[0] != 'B' || d[1] != 'M') return kErrFormat;
  uint32_t data_off = le32(d + 10);
  uint32_t hdr_size = le32(d + 14);
  if (hdr_size < 40) return kErrFormat;
  int32_t w = (int32_t)le32(d + 18);
  int32_t h = (int32_t)le32(d + 22);
  uint16_t bpp = le16(d + 28);
  uint32_t compression = le32(d + 30);
  bool bottom_up = h > 0;
  int32_t ah = bottom_up ? h : -h;
  if (w <= 0 || ah <= 0 || w > 1 << 16 || ah > 1 << 16) return kErrFormat;
  if (compression != 0 || (bpp != 8 && bpp != 24 && bpp != 32))
    return kErrFormat;

  const uint8_t* palette = nullptr;
  uint32_t pal_colors = 256;
  if (bpp == 8) {
    uint32_t colors = le32(d + 46);
    if (colors == 0 || colors > 256) colors = 256;
    // int64 arithmetic: uint32 sums here can wrap on crafted headers and
    // pass the check, leaving the pixel loop reading past the buffer.
    int64_t pal_off = 14 + (int64_t)hdr_size;
    int64_t pal_end = pal_off + 4 * (int64_t)colors;
    if (pal_end > (int64_t)data_off || pal_end > n) return kErrFormat;
    palette = d + pal_off;  // BGRA quads
    pal_colors = colors;    // pixel indices are clamped to this below
  }
  int64_t row_bytes = (((int64_t)w * bpp + 31) / 32) * 4;
  if ((int64_t)data_off + row_bytes * ah > n) return kErrBounds;

  img.h = ah;
  img.w = w;
  img.px.resize((size_t)ah * w);
  for (int32_t y = 0; y < ah; y++) {
    const uint8_t* row = d + data_off + row_bytes * (bottom_up ? ah - 1 - y : y);
    for (int32_t x = 0; x < w; x++) {
      double b, g, r;
      if (bpp == 8) {
        uint32_t ci = row[x];
        if (ci >= pal_colors) ci = pal_colors - 1;  // corrupt pixel index
        const uint8_t* q = palette + 4 * ci;
        b = q[0]; g = q[1]; r = q[2];
      } else {
        const uint8_t* q = row + (bpp / 8) * x;
        b = q[0]; g = q[1]; r = q[2];
      }
      img.px[(size_t)y * w + x] = (float)(0.299 * r + 0.587 * g + 0.114 * b);
    }
  }
  return 0;
}

int decode_any(const uint8_t* d, int64_t n, GrayImage& img) {
  if (n >= 2 && d[0] == 'P' && d[1] >= '2' && d[1] <= '6')
    return decode_pnm(d, n, img);
  if (n >= 2 && d[0] == 'B' && d[1] == 'M') return decode_bmp(d, n, img);
  return kErrFormat;
}

// Bilinear resize (align_corners=false, the cv2/PIL convention) into out.
void resize_bilinear(const GrayImage& img, int oh, int ow, float* out) {
  if (oh == img.h && ow == img.w) {
    memcpy(out, img.px.data(), sizeof(float) * (size_t)oh * ow);
    return;
  }
  double sy = (double)img.h / oh, sx = (double)img.w / ow;
  for (int y = 0; y < oh; y++) {
    double fy = (y + 0.5) * sy - 0.5;
    int y0 = (int)fy;
    if (fy < 0) { fy = 0; y0 = 0; }
    int y1 = y0 + 1 < img.h ? y0 + 1 : img.h - 1;
    double wy = fy - y0;
    for (int x = 0; x < ow; x++) {
      double fx = (x + 0.5) * sx - 0.5;
      int x0 = (int)fx;
      if (fx < 0) { fx = 0; x0 = 0; }
      int x1 = x0 + 1 < img.w ? x0 + 1 : img.w - 1;
      double wx = fx - x0;
      const float* p = img.px.data();
      double top = p[(size_t)y0 * img.w + x0] * (1 - wx) +
                   p[(size_t)y0 * img.w + x1] * wx;
      double bot = p[(size_t)y1 * img.w + x0] * (1 - wx) +
                   p[(size_t)y1 * img.w + x1] * wx;
      out[(size_t)y * ow + x] = (float)(top * (1 - wy) + bot * wy);
    }
  }
}

int load_file(const char* path, std::vector<uint8_t>& buf) {
  FILE* f = fopen(path, "rb");
  if (!f) return kErrRead;
  fseek(f, 0, SEEK_END);
  long sz = ftell(f);
  fseek(f, 0, SEEK_SET);
  if (sz <= 0) { fclose(f); return kErrRead; }
  buf.resize((size_t)sz);
  size_t got = fread(buf.data(), 1, (size_t)sz, f);
  fclose(f);
  return got == (size_t)sz ? 0 : kErrRead;
}

// ---- base64 (the connectors' wire form of a frame) ----

// Four tables, one per position in a quad, each holding its six bits where
// they land in a word whose low three bytes are the output's, first byte
// lowest; every
// character outside the alphabet reads kB64Bad, a bit no valid value has,
// so a whole payload is or-ed along and tested once.
constexpr uint32_t kB64Bad = 0x01000000u;

struct B64Tables {
  uint32_t t[4][256];
  B64Tables() {
    static const char kAlphabet[] =
        "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789+/";
    for (int p = 0; p < 4; p++)
      for (int c = 0; c < 256; c++) t[p][c] = kB64Bad;
    for (uint32_t v = 0; v < 64; v++) {
      uint8_t c = (uint8_t)kAlphabet[v];
      t[0][c] = v << 2;                                  // b0[7:2]
      t[1][c] = (v >> 4) | ((v & 0xFu) << 12);           // b0[1:0] b1[7:4]
      t[2][c] = ((v >> 2) << 8) | ((v & 0x3u) << 22);    // b1[3:0] b2[7:6]
      t[3][c] = v << 16;                                 // b2[5:0]
    }
  }
};

const B64Tables kB64;

}  // namespace

extern "C" {

// Probe dims without decoding pixels. Returns 0 and fills h/w on success.
int ocvf_probe(const uint8_t* data, int64_t len, int* h, int* w) {
  GrayImage img;
  int rc = decode_any(data, len, img);  // simple formats: decode IS cheap
  if (rc != 0) return rc;
  *h = img.h;
  *w = img.w;
  return 0;
}

// Decode + grayscale + resize to [out_h, out_w] float32 (0..255 range).
// out_h/out_w <= 0 means native size — caller must have probed.
int ocvf_decode_gray(const uint8_t* data, int64_t len, int out_h, int out_w,
                     float* out) {
  GrayImage img;
  int rc = decode_any(data, len, img);
  if (rc != 0) return rc;
  if (out_h <= 0 || out_w <= 0) {
    out_h = img.h;
    out_w = img.w;
  }
  resize_bilinear(img, out_h, out_w, out);
  return 0;
}

// File variant.
int ocvf_load_gray(const char* path, int out_h, int out_w, float* out) {
  std::vector<uint8_t> buf;
  int rc = load_file(path, buf);
  if (rc != 0) return rc;
  return ocvf_decode_gray(buf.data(), (int64_t)buf.size(), out_h, out_w, out);
}

// Pack a batch of files into one [count, out_h, out_w] float32 buffer.
// status[i] receives the per-file return code; returns number decoded OK.
int ocvf_load_batch(const char* const* paths, int count, int out_h, int out_w,
                    float* out, int* status) {
  int ok = 0;
  for (int i = 0; i < count; i++) {
    status[i] = ocvf_load_gray(paths[i], out_h, out_w,
                               out + (size_t)i * out_h * out_w);
    if (status[i] == 0) ok++;
  }
  return ok;
}

// Decode canonical base64 (RFC 4648 alphabet, '=' padding, no line breaks)
// into a caller-provided buffer. Returns the byte count written, or a
// negative code and an undefined buffer: kErrFormat for a length that is
// no multiple of four, a character outside the alphabet or padding
// anywhere but the last one or two places; kErrBounds when the decoded
// size exceeds ``capacity``. The caller falls back to the standard
// library's lenient decoder on any negative code.
int64_t ocvf_b64_decode(const uint8_t* text, int64_t n, uint8_t* out,
                        int64_t capacity) {
  if (n < 0 || (n & 3) != 0) return kErrFormat;
  if (n == 0) return 0;
  int pad = 0;
  if (text[n - 1] == '=') pad = text[n - 2] == '=' ? 2 : 1;
  const int64_t size = n / 4 * 3 - pad;
  if (size > capacity) return kErrBounds;
  const uint8_t* s = text;
  const uint8_t* last = text + n - 4;  // the one quad that may hold padding
  uint8_t* o = out;
  uint32_t bad = 0;
  for (; s < last; s += 4, o += 3) {
    uint32_t x = kB64.t[0][s[0]] | kB64.t[1][s[1]] | kB64.t[2][s[2]] |
                 kB64.t[3][s[3]];
    bad |= x;
    o[0] = (uint8_t)x;
    o[1] = (uint8_t)(x >> 8);
    o[2] = (uint8_t)(x >> 16);
  }
  uint32_t x = kB64.t[0][s[0]] | kB64.t[1][s[1]];
  if (pad < 2) x |= kB64.t[2][s[2]];
  if (pad < 1) x |= kB64.t[3][s[3]];
  bad |= x;
  if (bad & kB64Bad) return kErrFormat;
  o[0] = (uint8_t)x;
  if (pad < 2) o[1] = (uint8_t)(x >> 8);
  if (pad < 1) o[2] = (uint8_t)(x >> 16);
  return size;
}

}  // extern "C"
