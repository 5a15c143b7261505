// Python-free serving loader over the PJRT C API —
// the TPU-native analog of the reference's C serving API
// (ref: paddle/fluid/inference/capi/pd_predictor.cc:1 — serves a saved
// ProgramDesc from pure C; go/paddle/predictor.go:1).
//
// Loads the `save_compiled_inference_model` serving bundle
// (module.mlir.bc StableHLO bytecode + args/<i>.bin + serve_manifest.txt)
// against ANY PJRT plugin exporting GetPjrtApi — libtpu.so drives the
// TPU; a CPU plugin serves host-side.  No Python, no JAX, no protobuf
// library (the CompileOptions proto is hand-encoded: 4 bytes).
//
//   pjrt_serve <plugin.so> <bundle_dir>
//
// Prints each output's dtype/shape, first values, and an fp checksum.

#include <dlfcn.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

#define CHECK_OK(api, err)                                              \
  do {                                                                  \
    PJRT_Error* _e = (err);                                             \
    if (_e) {                                                           \
      PJRT_Error_Message_Args m;                                        \
      memset(&m, 0, sizeof m);                                          \
      m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;              \
      m.error = _e;                                                     \
      api->PJRT_Error_Message(&m);                                      \
      fprintf(stderr, "PJRT error at %s:%d: %.*s\n", __FILE__,          \
              __LINE__, (int)m.message_size, m.message);                \
      exit(1);                                                          \
    }                                                                   \
  } while (0)

namespace {

struct ArgSpec {
  std::string kind, name, dtype;
  std::vector<int64_t> dims;
};

size_t dtype_size(const std::string& d) {
  if (d == "float64" || d == "int64" || d == "uint64") return 8;
  if (d == "float32" || d == "int32" || d == "uint32") return 4;
  if (d == "float16" || d == "bfloat16" || d == "int16") return 2;
  if (d == "int8" || d == "uint8" || d == "bool") return 1;
  fprintf(stderr, "unknown dtype %s\n", d.c_str());
  exit(1);
}

PJRT_Buffer_Type buffer_type(const std::string& d) {
  if (d == "float32") return PJRT_Buffer_Type_F32;
  if (d == "float64") return PJRT_Buffer_Type_F64;
  if (d == "bfloat16") return PJRT_Buffer_Type_BF16;
  if (d == "float16") return PJRT_Buffer_Type_F16;
  if (d == "int64") return PJRT_Buffer_Type_S64;
  if (d == "int32") return PJRT_Buffer_Type_S32;
  if (d == "int16") return PJRT_Buffer_Type_S16;
  if (d == "int8") return PJRT_Buffer_Type_S8;
  if (d == "uint32") return PJRT_Buffer_Type_U32;
  if (d == "uint64") return PJRT_Buffer_Type_U64;
  if (d == "uint8") return PJRT_Buffer_Type_U8;
  if (d == "bool") return PJRT_Buffer_Type_PRED;
  fprintf(stderr, "unmapped dtype %s\n", d.c_str());
  exit(1);
}

std::vector<char> read_file(const std::string& path) {
  FILE* f = fopen(path.c_str(), "rb");
  if (!f) { fprintf(stderr, "cannot open %s\n", path.c_str()); exit(1); }
  fseek(f, 0, SEEK_END);
  long n = ftell(f);
  fseek(f, 0, SEEK_SET);
  std::vector<char> out(n);
  if (n && fread(out.data(), 1, n, f) != (size_t)n) {
    fprintf(stderr, "short read %s\n", path.c_str());
    exit(1);
  }
  fclose(f);
  return out;
}

void await_event(const PJRT_Api* api, PJRT_Event* ev) {
  if (!ev) return;
  PJRT_Event_Await_Args a;
  memset(&a, 0, sizeof a);
  a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  a.event = ev;
  CHECK_OK(api, api->PJRT_Event_Await(&a));
  PJRT_Event_Destroy_Args d;
  memset(&d, 0, sizeof d);
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  CHECK_OK(api, api->PJRT_Event_Destroy(&d));
}

}  // namespace

int main(int argc, char** argv) {
  if (argc != 3) {
    fprintf(stderr, "usage: %s <pjrt_plugin.so> <bundle_dir>\n", argv[0]);
    return 2;
  }
  const std::string plugin = argv[1], dir = argv[2];

  // -- manifest ---------------------------------------------------------
  std::string module_file;
  std::vector<ArgSpec> args_spec, outs_spec;
  {
    FILE* mf = fopen((dir + "/serve_manifest.txt").c_str(), "r");
    if (!mf) { fprintf(stderr, "no serve_manifest.txt in %s\n",
                       dir.c_str()); return 1; }
    char tag[16];
    while (fscanf(mf, "%15s", tag) == 1) {
      if (!strcmp(tag, "module")) {
        char buf[512];
        if (fscanf(mf, "%511s", buf) != 1) return 1;
        module_file = buf;
      } else if (!strcmp(tag, "arg") || !strcmp(tag, "out")) {
        int idx, nd;
        char kind[32] = "out", name[256] = "-", dt[32];
        if (!strcmp(tag, "arg")) {
          if (fscanf(mf, "%d %31s %255s %31s %d", &idx, kind, name, dt,
                     &nd) != 5) return 1;
        } else {
          if (fscanf(mf, "%d %31s %d", &idx, dt, &nd) != 3) return 1;
        }
        ArgSpec s;
        s.kind = kind; s.name = name; s.dtype = dt;
        for (int i = 0; i < nd; i++) {
          long long d;
          if (fscanf(mf, "%lld", &d) != 1) return 1;
          s.dims.push_back(d);
        }
        (!strcmp(tag, "arg") ? args_spec : outs_spec).push_back(s);
      }
    }
    fclose(mf);
  }

  // -- plugin -----------------------------------------------------------
  void* h = dlopen(plugin.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (!h) { fprintf(stderr, "dlopen: %s\n", dlerror()); return 1; }
  typedef const PJRT_Api* (*GetApiFn)();
  GetApiFn get_api = (GetApiFn)dlsym(h, "GetPjrtApi");
  if (!get_api) { fprintf(stderr, "no GetPjrtApi in %s\n",
                          plugin.c_str()); return 1; }
  const PJRT_Api* api = get_api();
  {
    PJRT_Plugin_Initialize_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    CHECK_OK(api, api->PJRT_Plugin_Initialize(&a));
  }

  PJRT_Client* client;
  {
    PJRT_Client_Create_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    CHECK_OK(api, api->PJRT_Client_Create(&a));
    client = a.client;
  }

  PJRT_Device* device;
  {
    PJRT_Client_AddressableDevices_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    a.client = client;
    CHECK_OK(api, api->PJRT_Client_AddressableDevices(&a));
    if (!a.num_addressable_devices) {
      fprintf(stderr, "no addressable devices\n");
      return 1;
    }
    device = a.addressable_devices[0];
  }

  // -- compile ----------------------------------------------------------
  std::vector<char> module = read_file(dir + "/" + module_file);
  // CompileOptionsProto: executable_build_options(3){num_replicas(4)=1,
  // num_partitions(5)=1} — proto3 wire format, no protobuf lib needed
  static const char kCompileOptions[] = {0x1a, 0x04, 0x20, 0x01,
                                         0x28, 0x01};
  PJRT_LoadedExecutable* exec;
  {
    PJRT_Program prog;
    memset(&prog, 0, sizeof prog);
    prog.struct_size = PJRT_Program_STRUCT_SIZE;
    prog.code = module.data();
    prog.code_size = module.size();
    static const char kFmt[] = "mlir";
    prog.format = kFmt;
    prog.format_size = sizeof kFmt - 1;
    PJRT_Client_Compile_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    a.client = client;
    a.program = &prog;
    a.compile_options = kCompileOptions;
    a.compile_options_size = sizeof kCompileOptions;
    CHECK_OK(api, api->PJRT_Client_Compile(&a));
    exec = a.executable;
  }
  fprintf(stderr, "compiled %s (%zu bytes) for device 0\n",
          module_file.c_str(), module.size());

  // -- stage args -------------------------------------------------------
  std::vector<std::vector<char>> host_args;
  std::vector<PJRT_Buffer*> dev_args;
  for (size_t i = 0; i < args_spec.size(); i++) {
    const ArgSpec& s = args_spec[i];
    host_args.push_back(read_file(dir + "/args/" + std::to_string(i)
                                  + ".bin"));
    size_t want = dtype_size(s.dtype);
    for (int64_t d : s.dims) want *= d;
    if (host_args.back().size() != want) {
      fprintf(stderr, "arg %zu (%s): %zu bytes on disk, want %zu\n", i,
              s.name.c_str(), host_args.back().size(), want);
      return 1;
    }
    PJRT_Client_BufferFromHostBuffer_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    a.client = client;
    a.data = host_args.back().data();
    a.type = buffer_type(s.dtype);
    a.dims = s.dims.data();
    a.num_dims = s.dims.size();
    a.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    a.device = device;
    CHECK_OK(api, api->PJRT_Client_BufferFromHostBuffer(&a));
    await_event(api, a.done_with_host_buffer);
    dev_args.push_back(a.buffer);
  }

  // -- execute ----------------------------------------------------------
  size_t n_out = outs_spec.size();
  std::vector<PJRT_Buffer*> out_buffers(n_out ? n_out : 1, nullptr);
  PJRT_Buffer** out_list = out_buffers.data();
  PJRT_Buffer* const* arg_list = dev_args.data();
  PJRT_Event* done = nullptr;
  {
    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof opts);
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_LoadedExecutable_Execute_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    a.executable = exec;
    a.options = &opts;
    a.argument_lists = &arg_list;
    a.num_devices = 1;
    a.num_args = dev_args.size();
    a.output_lists = &out_list;
    a.device_complete_events = &done;
    CHECK_OK(api, api->PJRT_LoadedExecutable_Execute(&a));
  }
  await_event(api, done);

  // -- fetch + print ----------------------------------------------------
  for (size_t i = 0; i < n_out; i++) {
    const ArgSpec& s = outs_spec[i];
    size_t nbytes = dtype_size(s.dtype);
    size_t nelem = 1;
    for (int64_t d : s.dims) nelem *= d;
    nbytes *= nelem;
    std::vector<char> host(nbytes);
    PJRT_Buffer_ToHostBuffer_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    a.src = out_buffers[i];
    a.dst = host.data();
    a.dst_size = nbytes;
    CHECK_OK(api, api->PJRT_Buffer_ToHostBuffer(&a));
    await_event(api, a.event);
    printf("out %zu dtype=%s shape=[", i, s.dtype.c_str());
    for (size_t d = 0; d < s.dims.size(); d++)
      printf("%s%lld", d ? "," : "", (long long)s.dims[d]);
    printf("] ");
    if (s.dtype == "float32") {
      const float* v = (const float*)host.data();
      double sum = 0;
      for (size_t k = 0; k < nelem; k++) sum += v[k];
      printf("first=[");
      for (size_t k = 0; k < nelem && k < 4; k++)
        printf("%s%g", k ? "," : "", v[k]);
      printf("] checksum=%g", sum);
    } else if (s.dtype == "int32") {
      const int* v = (const int*)host.data();
      long long sum = 0;
      for (size_t k = 0; k < nelem; k++) sum += v[k];
      printf("first=[");
      for (size_t k = 0; k < nelem && k < 4; k++)
        printf("%s%d", k ? "," : "", v[k]);
      printf("] checksum=%lld", sum);
    }
    printf("\n");
  }
  printf("PJRT_SERVE_OK outputs=%zu args=%zu\n", n_out, dev_args.size());
  return 0;
}
